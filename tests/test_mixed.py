"""Mixed-batch engine step + ragged paged attention.

* kernel numerics: the ragged Pallas kernel vs the jnp oracle (≤1e-4),
  including decode degeneration (C == 1), empty rows, short sequences and
  partially filled tail blocks;
* engine equivalence: the fused prefill+decode engine must emit
  bit-identical token streams to the serialized prefill-OR-decode engine,
  in strictly fewer iterations and with zero decode-starvation steps;
* the shift policy must see the combined (prefill + decode) token count;
* the persistent block-table host mirror must track the PagedKVCache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.policy import ThresholdPolicy
from repro.engine import ShiftEngine, EngineConfig, Request
from repro.engine import engine as engine_mod
from repro.engine.engine import balanced_chunk
from repro.kernels import ops
from repro.kernels import ref as R
from repro.models import build_model
from repro.models.model import Model
from repro.parallel import Layout


# ---------------------------------------------------------------------------
# ragged paged-attention kernel vs oracle
# ---------------------------------------------------------------------------
def _ragged_setup(B, C, Hq, Hkv, D, bs, nmax, ctx, ql, seed=0):
    """Paged pool + tables mapping ceil(ctx/bs) scattered physical blocks
    per row (unmapped tail = null block), matching engine invariants
    (q_lens <= ctx_lens, coverage reserved)."""
    ctx = np.asarray(ctx, np.int32)
    ql = np.asarray(ql, np.int32)
    nblocks = int(sum(-(-c // bs) for c in ctx)) + 1
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, C, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (nblocks, Hkv, bs, D), jnp.float32)
    vp = jax.random.normal(ks[2], (nblocks, Hkv, bs, D), jnp.float32)
    rng = np.random.default_rng(seed)
    phys = rng.permutation(np.arange(1, nblocks))
    bt = np.zeros((B, nmax), np.int32)
    pi = 0
    for b in range(B):
        nb = -(-ctx[b] // bs)
        bt[b, :nb] = phys[pi:pi + nb]
        pi += nb
    return (q, kp, vp, jnp.asarray(bt), jnp.asarray(ql), jnp.asarray(ctx))


@pytest.mark.parametrize("B,C,Hq,Hkv,D,bs,nmax,ctx,ql", [
    # mixed batch: full chunk, mid-chunk, decode row, empty padding row
    (4, 8, 4, 2, 64, 16, 8, [40, 8, 33, 0], [8, 8, 1, 0]),
    # pure decode (C == 1) with short seqs in a long table
    (3, 1, 8, 2, 64, 16, 16, [1, 17, 200], [1, 1, 1]),
    # block-tail edges: ctx exactly on / one past a block boundary, MHA
    (3, 4, 4, 4, 32, 8, 6, [8, 9, 31], [4, 2, 3]),
])
def test_ragged_kernel_matches_oracle(B, C, Hq, Hkv, D, bs, nmax, ctx, ql):
    q, kp, vp, bt, qlj, ctxj = _ragged_setup(B, C, Hq, Hkv, D, bs, nmax,
                                             ctx, ql)
    out = ops.paged_ragged_attention(q, kp, vp, bt, qlj, ctxj)
    g = Hq // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B, Hkv, g, C, D)
    want = R.paged_ragged_attention_ref(qf, kp, vp, bt, qlj, ctxj)
    want = want.reshape(B, Hq, C, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_ragged_kernel_decode_degenerates_to_padded():
    """C == 1 must reproduce the padded decode kernel (that kernel reads
    the head-minor ``[num_blocks, bs, Hkv, D]`` layout) to float32
    rounding. Not bitwise: that kernel folds one 16-token block at a time
    into its online softmax, the ragged kernel a grid step of P blocks."""
    q, kp, vp, bt, ql, ctx = _ragged_setup(4, 1, 8, 2, 64, 16, 8,
                                           [40, 8, 100, 128], [1, 1, 1, 1],
                                           seed=3)
    out = ops.paged_ragged_attention(q, kp, vp, bt, ql, ctx)
    want = ops.paged_decode_attention(q, kp.transpose(0, 2, 1, 3),
                                      vp.transpose(0, 2, 1, 3), bt, ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-6, rtol=0)


def test_ragged_kernel_empty_row_is_zero_and_isolated():
    """An empty row (ctx == 0) returns zeros and a poisoned null block must
    not leak into any row's output."""
    q, kp, vp, bt, ql, ctx = _ragged_setup(3, 4, 4, 2, 64, 16, 8,
                                           [40, 0, 16], [4, 0, 2], seed=7)
    out1 = ops.paged_ragged_attention(q, kp, vp, bt, ql, ctx)
    assert np.all(np.asarray(out1)[1] == 0.0)
    kp2 = kp.at[0].set(99.0)                   # poison the null block
    vp2 = vp.at[0].set(-99.0)
    out2 = ops.paged_ragged_attention(q, kp2, vp2, bt, ql, ctx)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_ragged_kernel_skips_unmapped_blocks():
    """Work-proportionality contract: garbage in blocks past each row's
    occupancy (mapped or not) cannot change the output."""
    q, kp, vp, bt, ql, ctx = _ragged_setup(2, 2, 4, 2, 64, 16, 8,
                                           [20, 35], [2, 2], seed=11)
    out1 = ops.paged_ragged_attention(q, kp, vp, bt, ql, ctx)
    # poison every block not covered by ctx (the pl.when-skipped ones)
    bs = 16
    keep = set()
    btn = np.asarray(bt)
    for b, c in enumerate([20, 35]):
        keep |= set(btn[b, :-(-c // bs)].tolist())
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for blk in range(kp2.shape[0]):
        if blk not in keep:
            kp2[blk], vp2[blk] = 55.0, -55.0
    out2 = ops.paged_ragged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                      bt, ql, ctx)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ---------------------------------------------------------------------------
# engine: mixed vs serialized equivalence
# ---------------------------------------------------------------------------
def _run_engine(m, params, mixed, prompts, n_new=6, burst=None, **kw):
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8, mixed=mixed,
                        **kw)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    reqs = [Request(i, p, max_new_tokens=n_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    if burst:
        # inject a prompt burst once decodes are in flight (same trigger
        # condition for both engines)
        for _ in range(200):
            eng.step()
            if any(r.generated for r in reqs):
                break
        for p in burst:
            nr = Request(100 + len(reqs), p, max_new_tokens=n_new)
            eng.add_request(nr)
            reqs.append(nr)
    eng.run_until_idle()
    assert all(len(r.generated) == n_new for r in reqs)
    return {r.rid: tuple(r.generated) for r in reqs}, eng


@pytest.mark.parametrize("arch", ["qwen3-8b", "qwen2-7b"])
def test_mixed_matches_serialized_bit_for_bit(arch):
    """Token streams must be identical; the mixed engine must use strictly
    fewer iterations and never run a step that starves ready decodes."""
    cfg = reduced_cfg(arch)
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(1, 12 + i)) for i in range(3)] + [list(range(2, 40))]
    burst = [list(range(3, 30)), list(range(5, 26))]
    g_mix, e_mix = _run_engine(m, params, True, prompts, burst=list(burst))
    g_ser, e_ser = _run_engine(m, params, False, prompts, burst=list(burst))
    assert e_mix.mixed and not e_ser.mixed
    assert g_mix == g_ser
    assert e_mix.step_count < e_ser.step_count
    starved = [s for s in e_mix.step_log
               if s["ready_decodes"] and not s["decode_tokens"]]
    assert not starved
    # the serialized engine DID starve decodes on the same workload — the
    # interference the mixed step removes
    assert any(s["ready_decodes"] and not s["decode_tokens"]
               for s in e_ser.step_log)
    # and the mixed engine really fused prefill with decode in one pass
    assert any(s["prefill_tokens"] and s["decode_tokens"]
               for s in e_mix.step_log)


def test_mixed_equivalence_under_memory_pressure():
    """Preemption + re-prefill through the fused path stays output
    invariant vs the serialized engine on a tight pool."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(1, 10 + i)) for i in range(6)]
    kw = dict(block_size=8, num_blocks=7)        # 6 usable blocks ≈ 2 seqs
    g_mix, e_mix = _run_engine(m, params, True, prompts, **kw)
    g_ser, _ = _run_engine(m, params, False, prompts, **kw)
    assert g_mix == g_ser
    assert e_mix.preemptions > 0                 # pressure was real
    assert e_mix.kv.num_used_blocks == 0         # no block leaks


@pytest.mark.parametrize("left,chunk,want", [
    (25, 8, [8, 8, 8]), (20, 8, [7, 7, 6]), (12, 8, [6, 6]), (9, 8, [8]),
    (2, 8, [2]), (300, 256, [150, 150]), (1025, 256, [256] * 4), (1, 8, []),
])
def test_balanced_chunks(left, chunk, want):
    """Prefill chunks reach the last known token's position in the fewest
    steps and share the tokens near-equally; where equal shares would
    overrun the chunk the 1-token tail rides the decode path."""
    got = []
    while (q := balanced_chunk(left, chunk)):
        got.append(q)
        left -= q
    assert got == want
    assert left in (0, 1)


def test_mixed_launch_shapes_are_few():
    """A decode-only step launches every slot's row, a step that carries a
    prefill chunk launches at the chunk width, and every launch carries
    the whole block table: each config meets one shape per row bucket."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(1, 12 + 7 * i)) for i in range(3)]
    _, eng = _run_engine(m, params, True, prompts)
    recs = [r for r in eng.step_log if r["config"]]
    nmax = eng.kv.max_blocks_per_seq
    assert recs and all(r["bucket"][2] == nmax for r in recs)
    pre = {tuple(r["bucket"]) for r in recs if r["prefill_tokens"]}
    dec = {tuple(r["bucket"]) for r in recs if not r["prefill_tokens"]}
    assert pre and all(c == 8 for _, c, _ in pre)
    assert dec == {(4, 1, nmax)}


@pytest.mark.parametrize("n_pre,n_dec,chunk,riding", [
    (1, 15, 256, 0),     # 15 rows of 256 padded tokens: all wait
    (3, 13, 256, 1),     # the prefill rows pad to 4: one rides
    (12, 4, 256, 4),     # the prefill rows pad to 16 anyway: all ride
    (1, 3, 256, 3),      # 3 x 256 padded tokens, within 1024: all ride
])
def test_decode_rows_ride_where_their_padding_is_cheap(n_pre, n_dec, chunk,
                                                       riding):
    """Decode rows ride in a launch carrying prefill chunks while the
    padded tokens they add stay within ``DECODE_RIDE_TOKENS`` (1024), or
    in the rows the prefill rows pad anyway; the rest take the next step.
    The configs do not enter: under ThresholdPolicy(32) the launch runs
    base and the decode rows alone would run shift, under
    ThresholdPolicy(4) both run base, and the same rows ride."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    ecfg = EngineConfig(max_slots=16, s_max=64, prefill_chunk=chunk,
                        block_size=8)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(32))
    reqs = [Request(i, [1, 2, 3], max_new_tokens=2)
            for i in range(n_pre + n_dec)]
    for i, r in enumerate(reqs):
        r.slot = i
    rows = ([(r, 0, chunk, False) for r in reqs[:n_pre]]
            + [(r, 3, 1, True) for r in reqs[n_pre:]])
    dec = set(reqs[n_pre:])
    for threshold in (32, 4):
        eng.policy = ThresholdPolicy(threshold)
        eng._decode_turn = False
        kept, rode = eng._ride(rows, dec, chunk)
        assert len(rode) == riding and rode <= dec
        assert [e for e in kept if e[0] not in dec] == rows[:n_pre]
        assert [e for e in kept if e[0] in dec] == [
            e for e in rows if e[0] in rode]
        assert eng._decode_turn == (riding < n_dec)


def test_decode_rows_left_out_take_the_next_step(monkeypatch):
    """With no padded tokens allowed, a step carrying prefill chunks
    launches decode rows only in the rows its prefill rows pad anyway; the
    next step launches the decode rows alone, and the token streams stay
    those of the serialized engine. One prompt first, so that the burst's
    two prefill rows find no built bucket larger than their own."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(2, 40))]
    burst = [list(range(3, 30)), list(range(5, 26))]
    monkeypatch.setattr(engine_mod, "DECODE_RIDE_TOKENS", 0)
    g_mix, e_mix = _run_engine(m, params, True, prompts, burst=list(burst))
    g_ser, _ = _run_engine(m, params, False, prompts, burst=list(burst))
    assert g_mix == g_ser
    recs = [r for r in e_mix.step_log if r["config"]]
    left = [i for i, r in enumerate(recs) if r["prefill_tokens"]
            and r["decode_tokens"] < r["ready_decodes"]]
    assert left, "no step left a ready decode row out"
    for i in left:
        nxt = recs[i + 1]
        assert not nxt["prefill_tokens"] and nxt["decode_tokens"]
        assert nxt["bucket"][:2] == [4, 1]



@pytest.mark.parametrize("warmed,rows", [
    ((), 1),                       # nothing built: the pow2 bucket
    (((4, 8),), 4),                # a warm-up's 4 x 8 holds the one row
    (((2, 8), (4, 8)), 2),         # the smallest built bucket that holds it
    (((2, 1),), 1),                # a bucket at another chunk does not
])
def test_prefill_launch_takes_the_smallest_built_bucket(warmed, rows):
    """A step that carries a prefill chunk launches in the fewest rows that
    hold its own among the shapes a ``forward`` program has already run at
    its chunk width, a warm-up's direct calls included; the tokens do not
    change."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    nmax = eng.kv.max_blocks_per_seq
    for R, C in warmed:                # as a warm-up runs a bucket
        z = [jnp.zeros((R, C), jnp.int32), jnp.zeros((R,), jnp.int32),
             jnp.zeros((R,), jnp.int32), jnp.zeros((R, nmax), jnp.int32)]
        _, eng.cache = eng.deploy.forward["base"](params, eng.cache, *z)
    assert eng.deploy.built == set(warmed)
    r = Request(0, list(range(1, 15)), max_new_tokens=3)
    eng.add_request(r)
    eng.run_until_idle()
    recs = [x for x in eng.step_log if x["config"]]
    assert recs[0]["prefill_tokens"] and recs[0]["bucket"][:2] == [rows, 8]
    fresh = ShiftEngine(m, m, params, params, ecfg,
                        policy=ThresholdPolicy(4))
    ref = Request(0, list(range(1, 15)), max_new_tokens=3)
    fresh.add_request(ref)
    fresh.run_until_idle()
    assert r.generated == ref.generated

def test_policy_sees_combined_mixed_tokens():
    """ThresholdPolicy must be fed prefill + decode tokens of the fused
    batch, with the prefill share passed separately."""
    seen = []

    class Recorder:
        def use_base(self, n_tokens, n_prefill=0):
            seen.append((n_tokens, n_prefill))
            return True

    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=Recorder())
    assert eng.mixed
    eng.add_request(Request(0, list(range(1, 6)), max_new_tokens=8))
    eng.add_request(Request(1, list(range(1, 40)), max_new_tokens=2))
    eng.run_until_idle()
    fused = [(n, p) for n, p in seen if p and n > p]
    assert fused, f"no fused prefill+decode batch in {seen}"
    assert all(n == p + (n - p) and n > p > 0 for n, p in fused)


def test_block_table_mirror_tracks_kv():
    """The persistent host mirror must equal the PagedKVCache tables after
    a run with growth, frees and preemptions."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    prompts = [list(range(1, 12 + i)) for i in range(5)]
    _, eng = _run_engine(m, params, True, prompts, block_size=8, num_blocks=9)
    assert eng.preemptions > 0
    eng._refresh_block_tables()                  # sync pending frees
    np.testing.assert_array_equal(eng._bt_host, eng.kv.table)


def test_mixed_requires_paged():
    cfg = reduced_cfg("mamba2-1.3b")             # recurrent: dense fallback
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    eng = ShiftEngine(m, m, params, params,
                      EngineConfig(max_slots=2, s_max=32))
    assert not eng.mixed                         # auto falls back with paged
    with pytest.raises(ValueError):
        ShiftEngine(m, m, params, params,
                    EngineConfig(max_slots=2, s_max=32, mixed=True))


def test_mixed_forward_shared_pool_across_base_and_shift(mesh122):
    """Zero-copy switching through the unified forward: mixed steps under
    the base (SP,TP) config and the shift (TP) config over the SAME paged
    pool must match the single-device run (ragged last-token extraction
    psums across sp ranks)."""
    cfg = reduced_cfg("qwen3-8b")
    ref = build_model(cfg, dtype=jnp.float32)
    pr = ref.init_params(jax.random.key(0))
    lay = Layout.from_mesh(mesh122, dp=("data",), sp=("sp",), tp=("tp",))
    mb = Model(cfg=cfg, lay=lay, mesh=mesh122, dtype=jnp.float32)
    ms = Model(cfg=cfg, lay=lay.to_shift(), mesh=mesh122, dtype=jnp.float32)
    pb = mb.init_params(jax.random.key(0))
    ps = ms.init_params(jax.random.key(0))

    B, bs, nmax = 8, 8, 4
    bt = jnp.asarray(1 + np.arange(B * nmax).reshape(B, nmax), jnp.int32)
    toks = jax.random.randint(jax.random.key(1), (B, 16), 0, cfg.vocab_size)
    offs = jnp.zeros((B,), jnp.int32)
    ql = jnp.full((B,), 16, jnp.int32)
    one = jnp.ones((B,), jnp.int32)

    pool_ref = ref.init_paged_cache(B * nmax + 1, bs)
    fwd_ref = jax.jit(ref.forward_fn())
    t_ref, pool_ref = fwd_ref(pr, pool_ref, toks, ql, offs, bt)

    pool = mb.init_paged_cache(B * nmax + 1, bs)
    fwd_b, fwd_s = jax.jit(mb.forward_fn()), jax.jit(ms.forward_fn())
    t, pool = fwd_b(pb, pool, toks, ql, offs, bt)   # prefill under base
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t_ref))
    offs = jnp.full((B,), 16, jnp.int32)
    for step in range(4):                           # alternate configs
        t_ref, pool_ref = fwd_ref(pr, pool_ref,
                                  t_ref.astype(jnp.int32)[:, None], one,
                                  offs, bt)
        shift = step % 2 == 0
        tk = t.astype(jnp.int32)[:, None]
        if not shift:                               # chunk axis covers sp=2
            tk = jnp.pad(tk, ((0, 0), (0, 1)))
        t, pool = (fwd_s if shift else fwd_b)(ps if shift else pb, pool,
                                              tk, one, offs, bt)
        np.testing.assert_array_equal(np.asarray(t), np.asarray(t_ref),
                                      err_msg=f"step {step}")
        offs = offs + 1


def test_trace_windows_are_bounded():
    """config_trace/step_times/step_log must stop growing past the rolling
    window while the totals keep counting."""
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    ecfg = EngineConfig(max_slots=2, s_max=64, prefill_chunk=8)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    eng.trace_window = 8                         # tiny window for the test
    eng.add_request(Request(0, list(range(1, 10)), max_new_tokens=20))
    eng.run_until_idle()
    assert eng.step_count > 8
    assert len(eng.config_trace) <= 8
    assert len(eng.step_times) <= 8
    assert len(eng.step_log) <= 8
    assert sum(eng.config_counts.values()) > 8
    assert eng.total_step_time >= sum(eng.step_times)
