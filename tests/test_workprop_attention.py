"""Work-proportional paged attention in the model (kernel dispatch).

* bitwise parity: the jnp mirror (the CPU "reference" backend the tier-1
  suite runs on) must be BITWISE equal to interpret-mode execution of the
  Pallas program, across GQA ratios, sliding windows, soft caps, empty
  rows and partially filled tail blocks;
* numerics vs the retained materialized-gather oracle (<=1e-4) — the only
  place ``_paged_gather`` survives;
* the dispatch layer: KernelConfig validation, env override, and the
  model's paged forward producing identical tokens under reference and
  interpret backends on a dp×sp×tp mesh in both base and shift configs;
* ``verify_paged_invariance`` holds for pools POPULATED through the
  kernel path (not just structurally);
* the ``s_max % chunk != 0`` tail: chunk overhang past the block table
  routes to the null block explicitly — engine streams stay bit-identical
  between the mixed and serialized paths, and the ref oracle's OOB gather
  clamps (``mode="clip"``);
* ``step_log.attn_ctx_tokens`` witnesses work-proportionality from traces
  alone.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.invariance import verify_paged_invariance
from repro.core.policy import ThresholdPolicy
from repro.engine import ShiftEngine, EngineConfig, Request
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.ops import KernelConfig
from repro.models import build_model
from repro.models.model import Model
from repro.parallel import Layout
from jax.sharding import PartitionSpec as P


def _setup(B, C, Hq, Hkv, D, bs, nmax, ctx, ql, seed=0):
    """Pool + tables mapping ceil(ctx/bs) scattered blocks per row, capped
    at the table width (a degenerate-prefill ctx may overhang the table —
    the overhung positions are absent); unmapped tail = null block,
    engine invariants otherwise (ql <= ctx)."""
    ctx = np.asarray(ctx, np.int32)
    ql = np.asarray(ql, np.int32)
    nbs = [min(-(-int(c) // bs), nmax) for c in ctx]
    nblocks = sum(nbs) + 1
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, C, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (nblocks, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (nblocks, Hkv, bs, D), jnp.float32)
    rng = np.random.default_rng(seed)
    phys = rng.permutation(np.arange(1, nblocks))
    bt = np.zeros((B, nmax), np.int32)
    pi = 0
    for b, nb in enumerate(nbs):
        bt[b, :nb] = phys[pi:pi + nb]
        pi += nb
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(ql), jnp.asarray(ctx))


CASES = [
    # B, C, Hq, Hkv, D, bs, nmax, ctx, ql, window, soft_cap
    (4, 8, 8, 2, 64, 16, 8, [40, 8, 33, 0], [8, 8, 1, 0], 0, 0.0),   # GQA 4:1
    (3, 4, 4, 4, 32, 8, 6, [8, 9, 31], [4, 2, 3], 0, 0.0),           # MHA, tails
    (3, 1, 8, 1, 64, 16, 16, [1, 17, 200], [1, 1, 1], 0, 0.0),       # MQA decode
    (4, 8, 8, 2, 64, 16, 8, [40, 8, 33, 16], [8, 8, 1, 4], 12, 0.0),  # window
    (3, 4, 4, 2, 32, 8, 8, [30, 64, 5], [4, 4, 2], 7, 0.0),          # window tails
    (4, 8, 8, 2, 64, 16, 8, [40, 8, 33, 0], [8, 8, 1, 0], 0, 30.0),  # soft cap
    (3, 4, 4, 2, 32, 8, 8, [30, 64, 5], [4, 4, 2], 9, 20.0),         # both
    # degenerate-prefill overhang: ctx past the table (s_max % chunk != 0
    # padding) — positions beyond nmax*bs are absent, never wrapped/clipped
    (2, 8, 4, 2, 32, 8, 4, [36, 20], [8, 8], 0, 0.0),
    # rows over several grid steps: bs 64 gives P = 8 blocks a step.
    # Table width 10, not a multiple of P: the last step is 2 blocks wide
    (3, 4, 4, 2, 32, 64, 10, [600, 130, 300], [1, 4, 2], 0, 0.0),
    # occupancy ending mid-step beside rows that fill whole steps
    (3, 2, 4, 2, 32, 64, 16, [1024, 699, 512], [2, 1, 2], 0, 0.0),
    # an empty row between two live rows: nothing prefetches row 2's
    # first step, so it fetches its own blocks
    (3, 2, 4, 2, 32, 64, 16, [700, 0, 900], [2, 0, 1], 0, 0.0),
    # window whose first live block falls mid-step (blocks 4 and 12)
    (3, 4, 4, 2, 32, 64, 24, [900, 1400, 300], [4, 2, 3], 600, 0.0),
]


@pytest.mark.parametrize("B,C,Hq,Hkv,D,bs,nmax,ctx,ql,window,cap", CASES)
def test_reference_bitwise_matches_interpret(B, C, Hq, Hkv, D, bs, nmax,
                                             ctx, ql, window, cap):
    """The dispatch's CPU fallback IS the kernel: same algorithm, same op
    order, bitwise-equal output to interpret-mode Pallas."""
    q, kp, vp, bt, qlj, ctxj = _setup(B, C, Hq, Hkv, D, bs, nmax, ctx, ql)
    ref = ops.paged_ragged_attention(q, kp, vp, bt, qlj, ctxj, window=window,
                                     soft_cap=cap,
                                     kcfg=KernelConfig("reference"))
    itp = ops.paged_ragged_attention(q, kp, vp, bt, qlj, ctxj, window=window,
                                     soft_cap=cap,
                                     kcfg=KernelConfig("interpret"))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(itp))


@pytest.mark.parametrize("B,C,Hq,Hkv,D,bs,nmax,ctx,ql,window,cap", CASES)
def test_kernel_matches_gather_oracle(B, C, Hq, Hkv, D, bs, nmax, ctx, ql,
                                      window, cap):
    """Numerics vs the independently-written materialized-gather oracle —
    only REAL ragged columns compare (padding columns are don't-care by
    contract and the two paths are free to disagree on them)."""
    q, kp, vp, bt, qlj, ctxj = _setup(B, C, Hq, Hkv, D, bs, nmax, ctx, ql)
    out = np.asarray(ops.paged_ragged_attention(
        q, kp, vp, bt, qlj, ctxj, window=window, soft_cap=cap,
        kcfg=KernelConfig("reference")))
    want = np.asarray(ops.paged_ragged_attention(
        q, kp, vp, bt, qlj, ctxj, window=window, soft_cap=cap,
        kcfg=KernelConfig("gather")))
    for b in range(B):
        n = int(ql[b])
        np.testing.assert_allclose(out[b, :n], want[b, :n],
                                   atol=1e-4, rtol=1e-4)


def test_window_low_blocks_are_inert():
    """Blocks entirely below every real row's sliding window are skipped:
    poisoning them (they are still mapped in the table) cannot change any
    real column's output."""
    B, C, Hq, Hkv, D, bs, nmax = 2, 2, 4, 2, 32, 8, 8
    ctx, ql, window = [50, 61], [2, 2], 10
    q, kp, vp, bt, qlj, ctxj = _setup(B, C, Hq, Hkv, D, bs, nmax, ctx, ql,
                                      seed=5)
    out1 = np.asarray(ops.paged_ragged_attention(
        q, kp, vp, bt, qlj, ctxj, window=window,
        kcfg=KernelConfig("interpret")))
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    btn = np.asarray(bt)
    for b in range(B):
        lo = max(ctx[b] - ql[b] - window + 1, 0) // bs
        for ib in range(lo):                    # mapped but out-of-window
            kp2[btn[b, ib]] = 77.0
            vp2[btn[b, ib]] = -77.0
    out2 = np.asarray(ops.paged_ragged_attention(
        q, jnp.asarray(kp2), jnp.asarray(vp2), bt, qlj, ctxj, window=window,
        kcfg=KernelConfig("interpret")))
    for b in range(B):
        np.testing.assert_array_equal(out1[b, :ql[b]], out2[b, :ql[b]])


def test_window_matches_dense_attend():
    """Sliding-window kernel numerics against the model's dense attend on
    the gathered contiguous view (the semantics ring layers will need)."""
    from repro.models.attention_math import attend
    B, C, Hq, Hkv, D, bs, nmax = 2, 4, 4, 2, 32, 8, 6
    ctx, ql, window = [40, 23], [4, 3], 11
    q, kp, vp, bt, qlj, ctxj = _setup(B, C, Hq, Hkv, D, bs, nmax, ctx, ql,
                                      seed=9)
    out = np.asarray(ops.paged_ragged_attention(
        q, kp, vp, bt, qlj, ctxj, window=window,
        kcfg=KernelConfig("reference")))
    kg = R._paged_gather(kp.transpose(0, 2, 1, 3), bt)     # head-minor view
    vg = R._paged_gather(vp.transpose(0, 2, 1, 3), bt)
    qpos = ctxj[:, None] - qlj[:, None] + jnp.arange(C)[None, :]
    want = np.asarray(attend(q, kg, vg, qpos, jnp.arange(nmax * bs),
                             causal=True, window=window, kv_len=ctxj))
    for b in range(B):
        np.testing.assert_allclose(out[b, :ql[b]], want[b, :ql[b]],
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------
def test_kernel_config_validates_backend():
    with pytest.raises(ValueError):
        KernelConfig("metal")
    assert KernelConfig("gather").resolve() == "gather"
    assert KernelConfig("interpret").resolve() == "interpret"


def test_kernel_config_env_override(monkeypatch):
    """CI forces the interpret backend through the environment so the
    Pallas program itself runs on the CPU matrix."""
    monkeypatch.setenv(ops.ATTN_BACKEND_ENV, "interpret")
    assert KernelConfig().resolve() == "interpret"
    # a typo must fail LOUDLY — CI's interpret leg depends on this env
    # var, and a silent fallback to the mirror would green-light a run
    # that never executed the Pallas program
    monkeypatch.setenv(ops.ATTN_BACKEND_ENV, "nonsense")
    with pytest.raises(ValueError):
        KernelConfig().resolve()
    monkeypatch.delenv(ops.ATTN_BACKEND_ENV)
    expected = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert KernelConfig().resolve() == expected
    # explicit choice wins over the environment
    monkeypatch.setenv(ops.ATTN_BACKEND_ENV, "interpret")
    assert KernelConfig("gather").resolve() == "gather"


def test_paged_gather_oob_clips():
    """The retained reference oracle pins jnp.take's OOB semantics: a table
    id past the pool clamps to the last block (mode="clip"), never an
    undefined fill."""
    pool = jnp.arange(4 * 2 * 1 * 3, dtype=jnp.float32).reshape(4, 2, 1, 3)
    oob = jnp.asarray([[1, 9]], jnp.int32)          # 9 >= num_blocks
    clamped = jnp.asarray([[1, 3]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(R._paged_gather(pool, oob)),
        np.asarray(R._paged_gather(pool, clamped)))


# ---------------------------------------------------------------------------
# model-level: the kernel runs inside shard_map, base AND shift configs
# ---------------------------------------------------------------------------
def _mesh_models(cfg, mesh, kcfg):
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    mb = Model(cfg=cfg, lay=lay, mesh=mesh, dtype=jnp.float32, kernel=kcfg)
    ms = Model(cfg=cfg, lay=lay.to_shift(), mesh=mesh, dtype=jnp.float32,
               kernel=kcfg)
    return mb, ms


def _drive_mixed(mb, ms, pb, ps, cfg, steps=3):
    """Prefill under base, then alternate shift/base decodes over the SAME
    pool; returns the token stream and the final pool."""
    B, bs, nmax = 8, 8, 4
    bt = jnp.asarray(1 + np.arange(B * nmax).reshape(B, nmax), jnp.int32)
    toks = jax.random.randint(jax.random.key(1), (B, 16), 0, cfg.vocab_size)
    offs = jnp.zeros((B,), jnp.int32)
    ql = jnp.full((B,), 16, jnp.int32)
    one = jnp.ones((B,), jnp.int32)
    pool = mb.init_paged_cache(B * nmax + 1, bs)
    fwd_b, fwd_s = jax.jit(mb.forward_fn()), jax.jit(ms.forward_fn())
    t, pool = fwd_b(pb, pool, toks, ql, offs, bt)
    stream = [np.asarray(t)]
    offs = jnp.full((B,), 16, jnp.int32)
    for step in range(steps):
        shift = step % 2 == 0
        tk = t.astype(jnp.int32)[:, None]
        if not shift:                               # chunk axis covers sp=2
            tk = jnp.pad(tk, ((0, 0), (0, 1)))
        t, pool = (fwd_s if shift else fwd_b)(ps if shift else pb, pool,
                                              tk, one, offs, bt)
        stream.append(np.asarray(t))
        offs = offs + 1
    return stream, pool


def test_mesh_backend_parity_base_and_shift(mesh222):
    """reference and interpret backends must produce BITWISE-identical
    token streams through the sharded model — prefill under the base
    (dp,sp,tp)=(2,2,2) config, decodes alternating shift/base — and the
    pools they write must match bitwise too (the scatter side is shared)."""
    cfg = reduced_cfg("qwen3-8b")
    streams, pools = {}, {}
    for backend in ("reference", "interpret"):
        mb, ms = _mesh_models(cfg, mesh222, KernelConfig(backend))
        pb = mb.init_params(jax.random.key(0))
        ps = ms.init_params(jax.random.key(0))
        streams[backend], pools[backend] = _drive_mixed(mb, ms, pb, ps, cfg)
    for a, b in zip(streams["reference"], streams["interpret"]):
        np.testing.assert_array_equal(a, b)
    for pa, pb_ in zip(jax.tree.leaves(pools["reference"]),
                       jax.tree.leaves(pools["interpret"])):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb_))


def test_mesh_kernel_close_to_gather_path(mesh222):
    """The kernel path's logits track the retired gather path (different
    summation order — allclose, not bitwise) through the same sharded
    forward."""
    cfg = reduced_cfg("qwen3-8b")
    logits = {}
    for backend in ("reference", "gather"):
        mb, ms = _mesh_models(cfg, mesh222, KernelConfig(backend))
        pb = mb.init_params(jax.random.key(0))
        B, bs, nmax = 8, 8, 4
        bt = jnp.asarray(1 + np.arange(B * nmax).reshape(B, nmax), jnp.int32)
        toks = jax.random.randint(jax.random.key(1), (B, 16), 0,
                                  cfg.vocab_size)
        pool = mb.init_paged_cache(B * nmax + 1, bs)
        fwd = jax.jit(mb.forward_fn(sample=False))
        lg, _ = fwd(pb, pool, toks, jnp.full((B,), 16, jnp.int32),
                    jnp.zeros((B,), jnp.int32), bt)
        logits[backend] = np.asarray(lg)
    np.testing.assert_allclose(logits["reference"], logits["gather"],
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_invariance_holds_on_kernel_written_pools(mesh122, backend):
    """§3.3.1 with data, on the kernel path (both its CPU mirror and the
    real Pallas program in interpret mode): a shared block prefilled ONCE
    under the base config and then READ by a shift-config pass over the
    same pool must stay bitwise untouched — the mixed kernel's null-block
    scatter routing for already-cached spans preserves the zero-copy
    SP↔TP switch, exactly as the retired gather path did."""
    cfg = reduced_cfg("qwen3-8b")
    kcfg = KernelConfig(backend)
    mb, ms = _mesh_models(cfg, mesh122, kcfg)
    pb = mb.init_params(jax.random.key(0))
    ps = ms.init_params(jax.random.key(0))
    B, bs, nmax = 2, 8, 4
    toks = jax.random.randint(jax.random.key(1), (B, 16), 1, cfg.vocab_size)
    # base config prefills row 0 into blocks [1, 2] (the shared prefix),
    # through the MIXED forward (q_lens == 16, the engine's production path)
    bt = np.zeros((B, nmax), np.int32)
    bt[0, :2] = (1, 2)
    pool = mb.init_paged_cache(B * nmax + 1, bs)
    ql = jnp.where(jnp.arange(B) == 0, 16, 0)
    _, pool = jax.jit(mb.forward_fn())(
        pb, pool, toks, ql, jnp.zeros((B,), jnp.int32), jnp.asarray(bt))
    shared = [1, 2]
    snap = jax.tree.map(lambda a: np.asarray(a).copy(), pool)
    # shift config runs row 1, which MAPS the shared blocks (reads them
    # through its table) and writes its own continuation blocks [3, 4]
    bt2 = np.zeros((B, nmax), np.int32)
    bt2[1, :2] = (1, 2)
    bt2[1, 2:4] = (3, 4)
    toks2 = jnp.where(jnp.arange(B)[:, None] == 1, toks, 0)
    ql2 = jnp.where(jnp.arange(B) == 1, 16, 0)
    _, pool = jax.jit(ms.forward_fn())(
        ps, pool, toks2, ql2, jnp.full((B,), 16, jnp.int32),
        jnp.asarray(bt2))
    lay = mb.lay
    isp = lambda x: isinstance(x, P)  # noqa: E731
    assert verify_paged_invariance(
        jax.tree.leaves(mb.abstract_paged_cache(B * nmax + 1, bs)),
        jax.tree.leaves(mb.paged_cache_specs(), is_leaf=isp),
        jax.tree.leaves(ms.paged_cache_specs(), is_leaf=isp),
        (B, nmax), mb.block_table_spec(), ms.block_table_spec(),
        mesh122, lay.model_axes,
        pool_base=snap, pool_shift=jax.tree.map(np.asarray, pool),
        shared_blocks=shared, dp_axes=lay.dp_axes)


# ---------------------------------------------------------------------------
# engine-level
# ---------------------------------------------------------------------------
def _run(m, params, mixed, prompts, n_new=5, **kw):
    ecfg = EngineConfig(mixed=mixed, **kw)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    reqs = [Request(i, p, max_new_tokens=n_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    eng.run_until_idle()
    assert all(len(r.generated) == n_new for r in reqs)
    return {r.rid: tuple(r.generated) for r in reqs}, eng


def test_engine_smax_chunk_overhang_tail():
    """s_max % prefill_chunk != 0: a chunk (and the mixed step's pow2 token
    bucket) overhangs the block table — those columns must route to the
    null block, never clip onto live KV. Mixed and serialized engines must
    stay bit-identical, and the null block is the only corrupted block."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    # s_max=52 -> nmax=7 blocks of 8 = 56 slots; prompts prefill to
    # offsets where off+chunk and the pow2 bucket run past 52
    kw = dict(max_slots=4, s_max=52, prefill_chunk=16, block_size=8)
    prompts = [list(range(1, 45 + i)) for i in range(3)]
    g_mix, e_mix = _run(m, params, True, prompts, **kw)
    g_ser, _ = _run(m, params, False, prompts, **kw)
    assert g_mix == g_ser
    assert e_mix.cfg.s_max % e_mix.cfg.prefill_chunk != 0
    # every real block still belongs to exactly one sequence: no leaks
    e_mix_used = e_mix.kv.num_used_blocks
    assert e_mix_used == 0                       # all retired


def test_engine_backend_gather_vs_kernel_streams():
    """A/B the retired gather path against the kernel path end-to-end:
    same engine, same workload, backend flipped via EngineConfig.kernel.
    Logit-level they differ only by summation order, so the greedy streams
    agree on this workload."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(1, 10 + i)) for i in range(3)]
    kw = dict(max_slots=4, s_max=64, prefill_chunk=8)
    g_k, e_k = _run(m, params, True, prompts,
                    kernel=KernelConfig("reference"), **kw)
    g_g, e_g = _run(m, params, True, prompts,
                    kernel=KernelConfig("gather"), **kw)
    assert g_k == g_g
    assert e_k.step_count == e_g.step_count


def test_step_log_attn_ctx_tokens_tracks_occupancy():
    """attn_ctx_tokens = sum of the batch rows' actual contexts — a trace
    alone verifies iteration cost follows occupancy, not s_max."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    ecfg = EngineConfig(max_slots=2, s_max=256, prefill_chunk=8)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    eng.add_request(Request(0, list(range(1, 10)), max_new_tokens=6))
    eng.run_until_idle()
    steps = [s for s in eng.step_log if s["decode_tokens"]
             or s["prefill_tokens"]]
    assert all("attn_ctx_tokens" in s for s in eng.step_log)
    # pure decode steps: one row whose context grows by one per step —
    # far below s_max at every step
    deco = [s["attn_ctx_tokens"] for s in steps if s["decode_tokens"]
            and not s["prefill_tokens"]]
    assert deco == sorted(deco)
    assert all(0 < c <= 9 + 6 < ecfg.s_max for c in deco)
    assert np.diff(deco).tolist() == [1] * (len(deco) - 1)
    # prefill steps count the chunk's end position
    pre = [s for s in steps if s["prefill_tokens"]]
    assert all(s["attn_ctx_tokens"] >= s["prefill_tokens"] for s in pre)


def test_adaptive_policy_prices_actual_context():
    """AdaptivePolicy fed real ctx_tokens must flip decisions where the
    S_max-blind proxy would not: a tiny decode batch over a HUGE context
    is memory-bound (-> favors tp/shift over sp)."""
    from repro.core.policy import AdaptivePolicy
    from repro.sim.costmodel import CostModel
    from repro.configs import get_config
    pol = AdaptivePolicy(CostModel(get_config("llama-70b")), sp=8, tp=1)
    # same token count, wildly different contexts
    lo = pol.use_base(4, 0, ctx_tokens=4 * 16, n_rows=4)
    hi = pol.use_base(4, 0, ctx_tokens=4 * 32768, n_rows=4)
    assert isinstance(lo, bool) and isinstance(hi, bool)
    # both callable without context (back-compat)
    assert isinstance(pol.use_base(4, 0), bool)


def test_roofline_hbm_traffic_kv_occupancy():
    """The dry-run's analytic decode/prefill cells can discount the cache
    read by the paged occupancy fraction: traffic must interpolate
    linearly in the cache term and leave weights/activations alone."""
    from types import SimpleNamespace
    from repro.roofline import hbm_traffic
    cfg = reduced_cfg("qwen3-8b")
    lay = Layout()
    dec = SimpleNamespace(kind="decode", global_batch=8, seq_len=1)
    pre = SimpleNamespace(kind="prefill", global_batch=8, seq_len=128)
    p_dev, c_dev = 1000.0, 400.0
    for shape, cache_mult in ((dec, 1.0), (pre, 2.0)):
        full = hbm_traffic(cfg, lay, shape, p_dev, c_dev)
        quarter = hbm_traffic(cfg, lay, shape, p_dev, c_dev,
                              kv_occupancy=0.25)
        assert full == hbm_traffic(cfg, lay, shape, p_dev, c_dev,
                                   kv_occupancy=1.0)
        assert full - quarter == pytest.approx(0.75 * cache_mult * c_dev)


def test_costmodel_work_prop_vs_gather_pricing():
    """The cost curves the tentpole changes: skewed batches cost the sum of
    their occupancies on the kernel path but rows x pow2(max) on the
    gather path."""
    from repro.sim.costmodel import CostModel, Strategy, _pow2
    from repro.configs import get_config
    cfg = get_config("llama-70b")
    wp = CostModel(cfg, attn_work_prop=True)
    ga = CostModel(cfg, attn_work_prop=False)
    skew = [8, 8, 8, 2000]
    t_wp = wp.iteration_time(0, 4, 0, Strategy("tp", 8), ctx_lens=skew)
    t_ga = ga.iteration_time(0, 4, 0, Strategy("tp", 8), ctx_lens=skew)
    assert t_wp < t_ga
    b_wp = wp.attn_hbm_bytes(skew)
    b_ga = ga.attn_hbm_bytes(skew)
    assert b_wp == pytest.approx(wp._kv_bytes_per_tok() * sum(skew))
    assert b_ga == pytest.approx(
        ga._kv_bytes_per_tok() * 4 * _pow2(2000) * ga.GATHER_COPY_FACTOR)
    # uniform full-context batches converge (modulo bucketing/copy factor)
    assert wp.iteration_time(0, 4, 0, Strategy("tp", 8),
                             ctx_lens=[2048] * 4) <= t_ga


@pytest.mark.skipif(os.environ.get(ops.ATTN_BACKEND_ENV) == "interpret",
                    reason="redundant when the whole run is interpret-forced")
def test_engine_runs_on_interpret_backend():
    """The CI fallback: a real engine run with the Pallas program in
    interpret mode must match the reference backend bit-for-bit."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(1, 9)), list(range(2, 12))]
    kw = dict(max_slots=2, s_max=32, prefill_chunk=8, n_new=3)
    g_ref, _ = _run(m, params, True, prompts,
                    kernel=KernelConfig("reference"), **kw)
    g_itp, _ = _run(m, params, True, prompts,
                    kernel=KernelConfig("interpret"), **kw)
    assert g_ref == g_itp
