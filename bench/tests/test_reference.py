"""The float32 reference agrees with the served model: prefill, then decode
through the paged cache, at a tiny size, for qkv bias (qwen2) and qk-norm
(qwen3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.reference.dense_gqa import Reference
from bench.tests import tiny


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen3-8b"])
def test_reference_logits_equal_program_logits(arch):
    """Same bf16-rounded weights on both sides, float32 compute: the paged
    prefill + decode logits equal the reference's full-sequence logits."""
    from repro.models.model import Model
    from repro.parallel import Layout
    spec, mc = tiny.tiny_spec(arch)
    model = Model(cfg=mc, lay=Layout(), mesh=None, dtype=jnp.float32)
    key = W.root_key(11)
    params = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32),
        W.program_tree(spec, k, 1, jnp.bfloat16)))(key)
    bs, nmax, C = 8, 8, 16
    pool = model.init_paged_cache(nmax + 1, bs)
    fwd = jax.jit(model.forward_fn(sample=False))
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, spec["vocab_size"], 21).tolist()
    bt = jnp.asarray((1 + np.arange(nmax))[None], jnp.int32)
    got, seq, off = [], list(prompt), 0
    with jax.default_matmul_precision("highest"):
        while off < len(prompt):        # chunked prefill, then decode
            n = min(C, len(prompt) - off)
            toks = np.zeros((1, C), np.int32)
            toks[0, :n] = prompt[off:off + n]
            logits, pool = fwd(params, pool, jnp.asarray(toks),
                               jnp.asarray([n], jnp.int32),
                               jnp.asarray([off], jnp.int32), bt)
            off += n
        got.append(np.asarray(logits[0]))
        for _ in range(8):
            seq.append(int(np.argmax(got[-1])))
            toks = np.zeros((1, C), np.int32)
            toks[0, 0] = seq[-1]
            logits, pool = fwd(params, pool, jnp.asarray(toks),
                               jnp.asarray([1], jnp.int32),
                               jnp.asarray([len(seq) - 1], jnp.int32), bt)
            got.append(np.asarray(logits[0]))
    ref = Reference(spec, 11)
    h = ref.hidden(np.asarray([seq + [0] * (512 - len(seq))], np.int32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(h[0, len(prompt) - 1:len(seq)] @ ref.head())
    got = np.stack(got)[:len(want)]
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(),
                               rtol=0)
    # and the served tokens read a gap of zero
    gaps = ref.served_gaps([seq], [len(seq) - len(prompt)])
    assert float(gaps[0].max()) <= 1e-4


def test_control_precision_reads_a_wide_gap():
    """The control (float8 matrix products) puts other tokens first: on a
    greedy continuation of the reference itself (program gap 0) its widest
    gap is well above zero."""
    spec, _ = tiny.tiny_spec("qwen2-1.5b")
    ref = Reference(spec, 3)
    rng = np.random.default_rng(2)
    seq = rng.integers(1, spec["vocab_size"], 20).tolist()
    with jax.default_matmul_precision("highest"):
        for _ in range(24):
            h = ref.hidden(np.asarray([seq + [0] * (512 - len(seq))]))
            seq.append(int(np.argmax(np.asarray(h[0, len(seq) - 1]
                                                @ ref.head()))))
    (g, c), = ref.served_gaps([seq], [24], control=True)
    assert float(g.max()) < 1e-3 < float(c.max())
