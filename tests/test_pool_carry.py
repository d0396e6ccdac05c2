"""The stacked paged pool rides in the layer scan's carry.

Each layer writes its K/V into the flat ``[L*N, Hkv, bs, Dh]`` view of the
pool at block ids offset by ``layer * N`` (``attention._pool_view``), so a
step neither slices a layer out of the pool nor restacks a fresh one:

* the compiled step's temporaries do not grow with the pool;
* layer i's K/V land in layer i's blocks, padding columns in layer i's own
  null block, and every other block keeps its bits.
"""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import reduced_cfg
from repro.models import blocks as BK
from repro.models import build_model
from repro.models import transformer as T


def _model():
    m = build_model(reduced_cfg("qwen3-8b"), dtype=jnp.float32)
    assert m.cfg.pattern_repeats >= 2 and not m.cfg.prefix_layers
    return m


def test_step_temporaries_do_not_grow_with_the_pool():
    """The mixed step at N and 4N blocks: its temporaries grow by less
    than one layer's share of the larger pool (a step that stacks a fresh
    pool holds at least the whole pool in temporaries)."""
    m = _model()
    step = jax.jit(m.forward_fn(paged=True), donate_argnums=(1,))
    params = jax.eval_shape(m.init_params, jax.random.key(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    temp, layer_bytes = [], 0
    for n in (64, 256):
        pool = m.abstract_paged_cache(n, 8)
        compiled = step.lower(params, pool, i32(4, 8), i32(4), i32(4),
                              i32(4, 8)).compile()
        temp.append(compiled.memory_analysis().temp_size_in_bytes)
        layer_bytes = sum(a.size * a.dtype.itemsize
                          for a in jax.tree.leaves(pool)) \
            // m.cfg.pattern_repeats
    assert temp[1] - temp[0] < layer_bytes, (temp, layer_bytes)


def test_each_layer_writes_its_own_blocks():
    """One mixed step: a row whose newest tokens land in the pool's last
    block (N-1), a padding column and a padding row. Each layer's written
    slots hold that layer's K/V and the row's logits are those of the
    layers run one by one against their own unstacked pools; the padding
    writes of layer i land in layer i's null block, as that layer's K/V;
    every other slot of every layer, null blocks included, is
    bit-identical."""
    m = _model()
    cfg, lay = m.cfg, m.lay
    n, bs = 16, 8
    params = m.init_params(jax.random.key(0))
    shapes = m.abstract_paged_cache(n, bs)
    keys = iter(jax.random.split(jax.random.key(1), 8))
    pool = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape, a.dtype), shapes)
    tokens = jnp.asarray([[5, 6, 7, 0], [0, 0, 0, 0]], jnp.int32)
    q_lens = jnp.asarray([3, 0], jnp.int32)
    offsets = jnp.asarray([5, 0], jnp.int32)     # row 0 writes slots 5..7
    bt = jnp.asarray([[n - 1, 0], [0, 0]], jnp.int32)

    before = jax.tree.map(np.asarray, pool)
    step = jax.jit(m.forward_fn(paged=True, sample=False))
    logits, after = step(params, pool, tokens, q_lens, offsets, bt)
    after = jax.tree.map(np.asarray, after)

    # the layers one by one, each against its own pool (no layer offset)
    pos = T._positions_prefill(tokens, offsets, lay)
    x = T._embed_tokens(params, tokens, pos, cfg, lay)
    ctx = {"offsets": offsets, "q_lens": q_lens, "block_tables": bt,
           "kcfg": m.kernel}
    own = []
    for i in range(cfg.pattern_repeats):
        x, c, _ = BK.block_prefill(
            jax.tree.map(lambda a: a[i], params["body"]["s0"]), "attn", x,
            jax.tree.map(lambda a: a[i], pool["body"]["s0"]), ctx, cfg, lay)
        own.append(jax.tree.map(np.asarray, c))
    # each layer also read its own blocks: row 0's newest token's logits
    h = T.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    np.testing.assert_allclose(
        logits[0], T.lmhead_apply(params["lm_head"], h[0, 2], lay),
        rtol=1e-4, atol=1e-4)

    # padding: row 0's column 3 (position 8) and row 1's columns 0..3 go
    # to the null block's slots 0..3, layer i's writes to layer i's own
    # null block; row 0's newest tokens to slots 5..7 of block N-1
    written = {0: [0, 1, 2, 3], n - 1: [5, 6, 7]}
    for name in ("k", "v"):
        b, a = before["body"]["s0"][name], after["body"]["s0"][name]
        for i in range(cfg.pattern_repeats):
            ref = own[i][name]
            np.testing.assert_allclose(a[i, n - 1][:, 5:8],
                                       ref[n - 1][:, 5:8],
                                       rtol=1e-5, atol=1e-5)
            # slot 0 is written twice (row 0's column 3, row 1's column 0)
            np.testing.assert_allclose(a[i, 0][:, 1:4], ref[0][:, 1:4],
                                       rtol=1e-5, atol=1e-5)
            for blk, slots in written.items():
                for slot in slots:
                    assert not np.array_equal(a[i, blk][:, slot],
                                              b[i, blk][:, slot]), \
                        (name, i, blk, slot)
            keep = np.ones((n, bs), bool)
            for blk, slots in written.items():
                keep[blk, slots] = False
            # [blocks, heads, bs, D]: compare slot by slot
            same = a[i].transpose(0, 2, 1, 3)[keep]
            assert np.array_equal(same, b[i].transpose(0, 2, 1, 3)[keep]), \
                (name, i)
