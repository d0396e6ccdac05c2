"""The served path against the plain float32 reference, on a (1, 2, 2) mesh.

Prompts are served through ``ShiftEngine`` on the paged mixed path with the
base config (SP2 x TP2, the Ulysses exchange around attention), the shift
config (TP4) and the paper's threshold rule, which runs prefill steps in
base and decode steps in shift, so that one request's cache is written by
one program and read by the other. At every launched row the logits that
the step computed for the row's newest position are compared with the
logits of ``bench/reference/dense_gqa.py``'s whole-sequence forward pass
(float32, ``highest`` precision, weights drawn again from the seed).

The model is the qwen3-8b reduced preset (q/k norm on) with 8 query and 4
KV heads, so that heads split over the mesh, in float32."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import weights as W  # noqa: E402
from bench.reference.dense_gqa import Q_BLOCK, Reference  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.policy import ThresholdPolicy  # noqa: E402
from repro.engine import EngineConfig, Request, ShiftEngine  # noqa: E402
from repro.engine import engine as engine_mod  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.parallel import Layout  # noqa: E402

SEED = 2**31 + 15
# Program and reference differ only in the order of float32 sums (blocked
# matmuls, the kernel's online softmax, psums over the mesh): their largest
# logit difference here reads 1e-7 to 2e-7 of the reference logits' range.
# Rounding once through bfloat16 (the weights alone, unrounded on one side)
# already moves it by 2e-3; leaving the exchange out, by far more.
RTOL = 1e-5


class Fixed:
    """A policy that always picks one config."""

    def __init__(self, base):
        self.base = base

    def use_base(self, n_tokens, n_prefill_tokens=0, **_):
        return self.base


def _spec_and_model():
    mc = dataclasses.replace(get_config("qwen3-8b").reduced(), num_heads=8,
                             num_kv_heads=4)
    assert mc.qk_norm
    spec = {"hidden_size": mc.d_model, "intermediate_size": mc.d_ff,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim, "num_hidden_layers": mc.num_layers,
            "vocab_size": mc.vocab_size, "rope_theta": mc.rope_theta,
            "rms_norm_eps": mc.norm_eps,
            "tie_word_embeddings": mc.tie_embeddings,
            "qkv_bias": mc.qkv_bias, "qk_norm": mc.qk_norm}
    return spec, mc


def _engine(policy):
    spec, mc = _spec_and_model()
    mesh = make_test_mesh(data=1, sp=2, tp=2)
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    base = Model(cfg=mc, lay=lay, mesh=mesh, dtype=jnp.float32)
    shift = Model(cfg=mc, lay=lay.to_shift(), mesh=mesh, dtype=jnp.float32)
    # the reference reads its weights rounded through bfloat16, as served
    draw = jax.jit(
        lambda k: jax.tree.map(lambda a: a.astype(jnp.float32),
                               W.program_tree(spec, k, base.lay.G)),
        out_shardings=base.shardings(base.param_specs()))
    p_base = draw(W.root_key(SEED))
    p_shift = jax.device_put(p_base, shift.shardings(shift.param_specs()))
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=16,
                        block_size=8, threshold=32)
    eng = ShiftEngine(base, shift, p_base, p_shift, ecfg, policy=policy)
    assert eng.paged and eng.mixed
    return spec, eng


def _serve(policy, stagger=False):
    """Serve three prompts (``stagger``: the first alone until it has a
    token); return the spec, the requests and, per launch, (config,
    tokens, q_lens, offsets, logits of each row's newest position)."""
    spec, eng = _engine(policy)
    launches = []
    for mode, fn in list(eng.deploy.forward.items()):
        model = eng.base if mode == "base" else eng.shift
        logits_fn = jax.jit(model.forward_fn(paged=True, sample=False))

        def spy(params, cache, toks, qlen, offs, bt, *rest, _fn=fn,
                _lg=logits_fn, _mode=mode):
            lg, _ = _lg(params, cache, toks, qlen, offs, bt, *rest)
            launches.append((_mode, np.asarray(toks), np.asarray(qlen),
                             np.asarray(offs), np.asarray(lg)))
            return _fn(params, cache, toks, qlen, offs, bt, *rest)
        eng.deploy.forward[mode] = spy
    rng = np.random.default_rng(3)
    V = spec["vocab_size"]
    reqs = [Request(i, rng.integers(1, V, n).tolist(), max_new_tokens=5)
            for i, n in enumerate((37, 22, 45))]
    for r in reqs:
        eng.add_request(r)
        while stagger and not r.generated:
            eng.step()
        stagger = False
    eng.run_until_idle()
    assert all(len(r.generated) == 5 for r in reqs)
    return spec, eng, reqs, launches


def _reference_logits(spec, seqs):
    S = -(-max(len(s) for s in seqs) // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), S), np.int32)
    for b, s in enumerate(seqs):
        toks[b, :len(s)] = s
    ref = Reference(spec, SEED)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.hidden(toks) @ ref.head())


def _compare(policy, stagger=False):
    """Largest |program - reference| logit over every launched row, as a
    share of the reference logits' range, and the configs that ran."""
    spec, eng, reqs, launches = _serve(policy, stagger)
    seqs = [list(r.prompt) + list(r.generated) for r in reqs]
    ref = _reference_logits(spec, seqs)
    worst, served, configs = 0.0, set(), set()
    for mode, toks, qlen, offs, lg in launches:
        for i in np.nonzero(qlen)[0]:
            o, q = int(offs[i]), int(qlen[i])
            b = next(b for b, s in enumerate(seqs)
                     if s[o:o + q] == toks[i, :q].tolist())
            p = o + q - 1                # the row's newest position
            want = ref[b, p]
            scale = float(want.max() - want.min())
            worst = max(worst, float(np.abs(lg[i] - want).max()) / scale)
            served.add((b, p))
            configs.add(mode)
    # every served token's position was compared
    for b, r in enumerate(reqs):
        for j in range(len(r.generated)):
            assert (b, len(r.prompt) - 1 + j) in served
    return worst, configs, eng


@pytest.mark.parametrize("name,policy,want", [
    ("base", Fixed(True), {"base"}),
    ("shift", Fixed(False), {"shift"}),
    ("threshold", ThresholdPolicy(32), {"base", "shift"}),
])
def test_served_logits_match_the_float32_reference(name, policy, want):
    worst, configs, eng = _compare(policy)
    assert configs == want
    assert worst < RTOL, (name, worst)
    if name == "threshold":
        # prefill steps (more than 32 batched tokens) ran base, decode
        # steps of up to 4 rows ran shift
        recs = [r for r in eng.step_log if r["config"]]
        assert all(r["config"] == "base" for r in recs
                   if r["prefill_tokens"] + r["decode_tokens"] > 32)
        assert all(r["config"] == "shift" for r in recs
                   if not r["prefill_tokens"])


def test_decode_turns_match_the_float32_reference(monkeypatch):
    """With no padded tokens allowed to decode rows (``DECODE_RIDE_TOKENS``
    0), prefill steps run base with the decode rows left out and the next
    steps decode them alone in shift: the logits still match. The first
    prompt is served alone until it decodes, so the other two prompts'
    chunks (26 tokens, over the threshold of 8: base) find no built row
    bucket with room for its decode row."""
    monkeypatch.setattr(engine_mod, "DECODE_RIDE_TOKENS", 0)
    worst, configs, eng = _compare(ThresholdPolicy(8), stagger=True)
    assert configs == {"base", "shift"}
    assert worst < RTOL, worst
    recs = [r for r in eng.step_log if r["config"]]
    assert any(r["prefill_tokens"] and r["decode_tokens"] < r["ready_decodes"]
               for r in recs)


def _no_exchange(x, axis_name, split_axis, concat_axis, tiled=False, **_):
    """``all_to_all`` with every chunk taken from this rank itself: the
    shapes of the exchange, none of its data."""
    assert tiled
    n = x.shape[split_axis]
    return jnp.concatenate(jnp.split(x, n, axis=split_axis), axis=concat_axis)


def test_base_without_the_ulysses_exchange_misses_the_reference(monkeypatch):
    monkeypatch.setattr(jax.lax, "all_to_all", _no_exchange)
    worst, configs, _ = _compare(Fixed(True))
    assert configs == {"base"}
    assert worst > 100 * RTOL
