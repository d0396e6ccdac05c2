"""Kernel + scheduling micro-benchmarks.

Wall time of the jnp reference paths and the Pallas kernels in interpret
mode on CPU (TPU timings are the roofline estimates in EXPERIMENTS.md
§Roofline), plus two comparisons the mixed-batch engine rests on:

* ragged-vs-padded paged attention — the padded kernel runs the full
  ``nmax`` grid per sequence; the ragged kernel ``pl.when``-skips blocks
  past each sequence's occupancy, and the engine additionally slices the
  table batch to the occupied bucket (``ragged_sliced`` — the shape the
  engine actually launches).
* work-proportional engine decode (``attn.*``) — a real paged ShiftEngine
  decoding a skewed batch under the kernel path vs the retired
  materialized-gather path (``KernelConfig("gather")``): per-step
  wall-clock (reported), the logged ``attn_ctx_tokens`` occupancy and the
  modeled gather/kernel HBM-bytes ratio (gated — the cost curve the
  kernel adoption changes).
* mixed-vs-serialized engine stepping — ServeSim replays the same bursty
  trace under the fused prefill+decode schedule and the serialized
  prefill-OR-decode schedule, costed by the roofline CostModel.
* prefix-cache reuse — the same shared-system-prompt trace with and
  without hash-indexed prefix caching (deterministic sim numbers: saved
  prefill tokens, TTFT ratio).
* dp=2 paged engine smoke — a real per-dp-row ShiftEngine (paged + mixed
  + prefix cache) on a 2×1×1 host mesh; gated on deterministic scheduling
  counters so a silent dense fallback under dp>1 fails CI.
* fault tolerance (``fault.*``) — the crash-recovery drill outcome
  (``fault.recovery_replay_ok``: 1.0 iff streams across a crash are
  exactly-once and bit-identical — gated, a drop to 0 fails CI), the
  terminal-outcome + zero-leak contract under a seeded storm, and the
  wall overhead of the fault-tolerance bookkeeping on the fault-free
  hot path (``fault.overhead_ratio``, relaxed gate like
  ``obs.overhead_ratio``).
* elastic resharding (``elastic.*``) — a dp=2 engine swaps its
  Deployment to merged pure-TP mid-decode and back
  (``elastic.reshard_replay_ok``: 1.0 iff streams stay bit-identical and
  zero blocks leak — gated), plus the deterministic re-pour volume and
  the extra iterations the swap cost (should be 0: it runs between
  iterations).

Emits CSV rows (legacy, for benchmarks/run.py) and writes a
machine-readable ``BENCH_kernels.json``:
``python benchmarks/kernels_bench.py [--smoke] [--out BENCH_kernels.json]``

``benchmarks/compare_bench.py`` gates CI on the deterministic subset of
these entries against the committed ``benchmarks/BENCH_baseline.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

# the dp=2 paged-engine smoke needs >= 2 (virtual) devices; harmless for
# every other bench (they ignore the extra CPU devices)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels import ref as R


def _t(fn, *args, iters=3):
    """Median per-call wall time in us (median, not mean: interpret-mode
    timings have heavy right tails — GC, first-touch paging — and the
    speedup ratios derived from these feed the CI regression gate)."""
    jax.block_until_ready(fn(*args))  # compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def _ref_benches(rec, iters):
    k = jax.random.key(0)
    q = jax.random.normal(k, (8, 512, 64), jnp.float32)
    kk = jax.random.normal(k, (4, 512, 64), jnp.float32)
    f = jax.jit(lambda a, b, c: R.flash_attention_ref(a, b, c))
    rec("ref.flash_512", _t(f, q, kk, kk, iters=iters), "us_per_call")

    qd = jax.random.normal(k, (8, 4, 2, 64), jnp.float32)
    kd = jax.random.normal(k, (8, 4, 1024, 64), jnp.float32)
    lens = jnp.full((8,), 800, jnp.int32)
    g = jax.jit(lambda a, b, c, l: R.decode_attention_ref(a, b, c, l))
    rec("ref.decode_1k", _t(g, qd, kd, kd, lens, iters=iters), "us_per_call")

    bs, nmax, nblocks = 16, 64, 512
    kpool = jax.random.normal(k, (nblocks, bs, 4, 64), jnp.float32)
    bt = jax.random.randint(k, (8, nmax), 1, nblocks).astype(jnp.int32)
    gp = jax.jit(lambda a, b, c, t, l: R.paged_decode_attention_ref(a, b, c, t, l))
    rec("ref.paged_decode_1k", _t(gp, qd, kpool, kpool, bt, lens, iters=iters),
        "us_per_call")

    x = jax.random.normal(k, (12, 64, 32), jnp.float32)
    b = jax.random.normal(k, (12, 64, 16), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(k, (12, 64, 1), jnp.float32))
    cum = jnp.cumsum(-dt * 0.5, axis=1)
    h = jax.jit(lambda *a: R.ssd_chunk_ref(*a))
    rec("ref.ssd_chunk", _t(h, x, b, b, dt, cum, iters=iters), "us_per_call")

    xn = jax.random.normal(k, (4096, 1024), jnp.float32)
    s = jnp.ones((1024,), jnp.float32)
    rn = jax.jit(lambda a, b: R.rmsnorm_ref(a, b))
    rec("ref.rmsnorm_4Mx", _t(rn, xn, s, iters=iters), "us_per_call")


def _ragged_vs_padded(rec, iters, smoke):
    """Short sequences (3 mapped blocks) against a long-s_max table: the
    padded grid pays nmax blocks of DMA+compute per sequence; the ragged
    kernel skips past the occupancy, and slicing the table to the occupied
    bucket (what the engine launches) shrinks the grid itself."""
    B, Hq, Hkv, D, bs = 8, 8, 2, 64, 16
    nmax = 32 if smoke else 64
    iters = max(iters, 5)    # the speedup ratios feed the CI gate — single
    #                          -iteration timings are too jittery to compare
    n_mapped, ctx = 3, 40                        # tokens resident per seq
    nblocks = B * n_mapped + 1
    k = jax.random.key(0)
    q = jax.random.normal(k, (B, 1, Hq, D), jnp.float32)
    kp = jax.random.normal(k, (nblocks, Hkv, bs, D), jnp.float32)   # head-major
    vp = jax.random.normal(k, (nblocks, Hkv, bs, D), jnp.float32)
    bt = np.zeros((B, nmax), np.int32)           # unmapped tail = null block
    bt[:, :n_mapped] = 1 + np.arange(B * n_mapped).reshape(B, n_mapped)
    lens = jnp.full((B,), ctx, jnp.int32)
    ones = jnp.ones((B,), jnp.int32)
    sliced = jnp.asarray(bt[:, :4])              # engine's pow2 bucket of 3
    # pin the interpret backend: the skip speedups measure the PALLAS
    # grid's pl.when behavior (the dispatch would otherwise hand CPU calls
    # to the jnp mirror, which computes skipped steps)
    from repro.kernels.ops import KernelConfig
    itp = KernelConfig("interpret")
    rag = lambda *a: ops.paged_ragged_attention(*a, kcfg=itp)  # noqa: E731
    # the padded decode kernel reads the head-minor [nblocks, bs, Hkv, D]
    t_pad = _t(ops.paged_decode_attention, q, kp.transpose(0, 2, 1, 3),
               vp.transpose(0, 2, 1, 3), jnp.asarray(bt), lens, iters=iters)
    t_rag = _t(rag, q, kp, vp, jnp.asarray(bt), ones, lens, iters=iters)
    t_sli = _t(rag, q, kp, vp, sliced, ones, lens, iters=iters)
    # the production CPU fallback (the kernel's jnp mirror) on the same
    # sliced shape — what tier-1 and the engine actually pay per call
    t_mir = _t(lambda *a: ops.paged_ragged_attention(
        *a, kcfg=KernelConfig("reference")), q, kp, vp, sliced, ones, lens,
        iters=iters)
    rec(f"paged.padded_nmax{nmax}", t_pad, "us_per_call")
    rec(f"paged.ragged_skip_nmax{nmax}", t_rag, "us_per_call")
    rec("paged.ragged_sliced", t_sli, "us_per_call")
    rec("paged.mirror_sliced", t_mir, "us_per_call")
    rec("paged.speedup_skip", t_pad / t_rag, "x")
    rec("paged.speedup_sliced", t_pad / t_sli, "x")


def _work_prop_attn(rec, emit, smoke):
    """End-to-end paged ENGINE decode steps: the work-proportional kernel
    path (the production default) vs the retired materialized-gather path
    (``KernelConfig("gather")``), same model, same skewed workload — one
    long row among short ones, so the gather pays every row at the
    pow2-bucketed max context while the kernel pays each row's own
    occupancy.

    Wall-clock per decode step is reported but NOT gated (CPU wall time
    cannot show the DMA skip — that is TPU behavior; ``paged.speedup_*``
    already gates the interpret-mode grid skip). The gated entries are
    deterministic: the engine-logged ``attn_ctx_tokens`` of the first
    all-decode step (the occupancy the kernel actually reads) and the
    modeled HBM-bytes ratio between gather and kernel pricing from the
    roofline CostModel — the cost curve the tentpole changes."""
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import ShiftEngine, EngineConfig, Request
    from repro.kernels.ops import KernelConfig
    from repro.models import build_model
    from repro.roofline.terms import H200
    from repro.sim.costmodel import CostModel

    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    long_len = 48 if smoke else 96
    prompts = [list(range(1, long_len + 1))] + \
              [list(range(1, 12 + i)) for i in range(3)]
    n_new = 4 if smoke else 8
    streams, ctx_decode = {}, 0
    for name, backend in (("work_prop", "reference"), ("gather", "gather")):
        ecfg = EngineConfig(max_slots=4, s_max=256, prefill_chunk=32,
                            block_size=16, kernel=KernelConfig(backend))
        eng = ShiftEngine(m, m, params, params, ecfg,
                          policy=ThresholdPolicy(4))
        reqs = [Request(i, p, max_new_tokens=n_new)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        while not eng.active \
                or not all(r.prefilled >= r.pos for r in eng.active):
            eng.step()                      # swallow the prompts
        eng.step()                          # warm-up: compile decode shape
        ts = []
        while any(not r.done for r in eng.active):
            t0 = time.perf_counter()
            eng.step()
            ts.append(time.perf_counter() - t0)
        eng.run_until_idle()
        ts.sort()
        ts = ts or [0.0]                    # all rows done in the warm-up
        streams[name] = {r.rid: tuple(r.generated) for r in reqs}
        if name == "work_prop":              # host-side log: backend-blind
            deco = [s for s in eng.step_log
                    if s["decode_tokens"] and not s["prefill_tokens"]]
            ctx_decode = deco[0]["attn_ctx_tokens"] if deco else 0
        rec(f"attn.{name}_decode_step_us", ts[len(ts) // 2] * 1e6,
            "us_per_call")
    # the two backends differ only by summation order; greedy streams can
    # legitimately diverge on a near-tie logit, so note it, don't fail the
    # whole benchmark job over an ulp (the bitwise contracts live in
    # tests/test_workprop_attention.py, same-backend only)
    if streams["work_prop"] != streams["gather"]:
        emit("# note: work_prop vs gather greedy streams diverged "
             "(summation-order near-tie)")
    rec("attn.decode_ctx_tokens", ctx_decode, "tokens")
    # modeled HBM bytes for that first all-decode step's composition
    ctxs = [len(p) + 1 for p in prompts]
    wp = CostModel(cfg, hw=H200, attn_work_prop=True)
    ga = CostModel(cfg, hw=H200, attn_work_prop=False)
    rec("attn.gather_bytes_ratio",
        ga.attn_hbm_bytes(ctxs) / wp.attn_hbm_bytes(ctxs), "x")


def _mixed_vs_serialized(rec, smoke):
    """Same bursty trace, two schedules, roofline-costed iterations."""
    from repro.configs import get_config
    from repro.roofline.terms import H200
    from repro.sim.costmodel import CostModel
    from repro.sim.simulator import ServeSim, SimRequest

    cfg = get_config("qwen3-8b")
    n_req = 16 if smoke else 64
    # bursts of long prompts landing while earlier requests decode — the
    # serialized schedule starves those decodes for whole iterations
    trace = [(0.2 * (i // 8), 512, 64) for i in range(n_req)]
    out = {}
    for mixed in (True, False):
        sim = ServeSim(CostModel(cfg, hw=H200), "shift", n_chips=8,
                       prefill_chunk=512, mixed=mixed)
        reqs = sim.run([SimRequest(i, t, ni, no)
                        for i, (t, ni, no) in enumerate(trace)])
        done = [r for r in reqs if r.finish >= 0]
        tpots = sorted(r.tpot for r in done if r.n_out > 1)
        name = "mixed" if mixed else "serialized"
        out[name] = dict(iters=sim.iterations, starved=sim.starved_steps,
                         tpot_p50=tpots[len(tpots) // 2],
                         tpot_p99=tpots[min(len(tpots) - 1,
                                            int(len(tpots) * 0.99))],
                         makespan=max(r.finish for r in done))
        rec(f"step.{name}_iterations", sim.iterations, "iters")
        rec(f"step.{name}_starved_steps", sim.starved_steps, "iters")
        rec(f"step.{name}_tpot_p50", out[name]["tpot_p50"] * 1e3, "ms")
        rec(f"step.{name}_tpot_p99", out[name]["tpot_p99"] * 1e3, "ms")
        rec(f"step.{name}_makespan", out[name]["makespan"], "s")
    rec("step.tpot_p50_ratio",
        out["serialized"]["tpot_p50"] / out["mixed"]["tpot_p50"], "x")
    rec("step.tpot_p99_ratio",
        out["serialized"]["tpot_p99"] / out["mixed"]["tpot_p99"], "x")


def _prefix_reuse(rec, smoke):
    """Shared-system-prompt trace, prefix cache off vs on (roofline-costed
    sim — deterministic, so CI can gate on these numbers exactly)."""
    from repro.configs import get_config
    from repro.roofline.terms import H200
    from repro.sim.costmodel import CostModel
    from repro.sim.simulator import ServeSim, SimRequest

    cfg = get_config("qwen3-8b")
    n_req = 16 if smoke else 64
    sys_len = 256                    # shared system prompt (16 blocks)
    trace = [(0.05 * i, sys_len + 64, 32, 0, sys_len) for i in range(n_req)]
    out = {}
    for on in (False, True):
        sim = ServeSim(CostModel(cfg, hw=H200), "shift", n_chips=8,
                       prefill_chunk=512, prefix_cache=on)
        reqs = sim.run([SimRequest(i, t, ni, no, prefix_id=p, prefix_len=pl)
                        for i, (t, ni, no, p, pl) in enumerate(trace)])
        done = [r for r in reqs if r.finish >= 0]
        ttfts = sorted(r.ttft for r in done)
        name = "warm" if on else "cold"
        out[name] = dict(saved=sim.prefill_tokens_saved,
                         ttft_p50=ttfts[len(ttfts) // 2])
        rec(f"prefix.{name}_ttft_p50", out[name]["ttft_p50"] * 1e3, "ms")
    rec("prefix.saved_tokens", out["warm"]["saved"], "tokens")
    rec("prefix.ttft_p50_ratio",
        out["cold"]["ttft_p50"] / out["warm"]["ttft_p50"], "x")


def _dp_paged_smoke(rec, emit):
    """End-to-end dp=2 paged+mixed+prefix ShiftEngine on a 2×1×1 host
    mesh: per-row block pools, free-block-aware routing, in-flight
    prefill sharing. The gated numbers are SCHEDULING outputs (iteration
    count, prefill tokens saved by the per-row prefix caches, preemptions)
    — deterministic integers, independent of wall clock — so CI catches a
    per-dp-row regression (e.g. the engine silently falling back to the
    dense cache again) as a hard failure."""
    if len(jax.devices()) < 2:
        emit("# dp_paged_smoke skipped: <2 devices "
             "(XLA_FLAGS was pre-set without host_platform_device_count)")
        return
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import (ShiftEngine, EngineConfig, PrefixConfig,
                              Request)
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.parallel import Layout

    cfg = get_config("qwen3-8b").reduced()
    mesh = make_test_mesh(data=2, sp=1, tp=1)
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    mb = Model(cfg=cfg, lay=lay, mesh=mesh, dtype=jnp.float32)
    ms = Model(cfg=cfg, lay=lay.to_shift(), mesh=mesh, dtype=jnp.float32)
    pb = mb.init_params(jax.random.key(0))
    ps = ms.init_params(jax.random.key(0))
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8, threshold=4,
                        block_size=8, prefix=PrefixConfig(enabled=True))
    eng = ShiftEngine(mb, ms, pb, ps, ecfg, policy=ThresholdPolicy(4))
    assert eng.paged and eng.dp == 2, eng.paged_disabled_reason
    shared = list(range(1, 17))                # 2 full blocks per row
    reqs = [Request(i, shared + list(range(100 + 3 * i, 104 + 3 * i)),
                    max_new_tokens=4) for i in range(8)]
    for r in reqs:
        eng.add_request(r)
    eng.run_until_idle(max_steps=500)
    assert all(r.finish_time is not None for r in reqs)
    s = eng.prefix_stats
    rec("dp.paged_iterations", eng.step_count, "iters")
    rec("dp.paged_prefill_tokens_saved", s["tokens_saved"], "tokens")
    rec("dp.paged_preemptions", eng.preemptions, "iters")


def _obs_bench(rec, smoke):
    """Observability cost on the live engine: the same workload stepped
    with the full instrumentation (metrics registry + lifecycle events +
    step records) and with ``EngineConfig(obs=False)``'s inert ``NullObs``.
    ``obs.overhead_ratio`` is the median-step wall ratio (instrumented /
    uninstrumented — wall-derived, so it gates at the relaxed speedup
    noise factor); ``obs.events_per_request`` counts emitted lifecycle
    events per request on the fixed workload — deterministic, so any
    schema/emission change shows up as an exact delta against the
    baseline."""
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import (ShiftEngine, EngineConfig, ObsConfig,
                              PrefixConfig, Request)
    from repro.models import build_model

    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    n_req = 4
    n_new = 4 if smoke else 8
    prompts = [list(range(1, 12 + 3 * i)) for i in range(n_req)]

    def run(obs_on):
        ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8,
                            prefix=PrefixConfig(enabled=True),
                            obs=ObsConfig(enabled=obs_on))
        eng = ShiftEngine(m, m, params, params, ecfg,
                          policy=ThresholdPolicy(4))
        for i, p in enumerate(prompts):
            eng.add_request(Request(i, p, max_new_tokens=n_new))
        eng.step()                          # warm-up: compile first shape
        ts = []
        while eng.active or eng.queue:
            t0 = time.perf_counter()
            eng.step()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2] if ts else 0.0, eng

    t_off, _ = run(False)                   # NullObs first: shares jit cache
    t_on, eng = run(True)
    rec("obs.overhead_ratio", (t_on / t_off) if t_off > 0 else 1.0, "x")
    rec("obs.events_per_request",
        len(eng.obs.events.events) / n_req, "x")
    rec("obs.step_records", len(eng.step_log), "iters")


def _fault_bench(rec, smoke):
    """Fault-tolerance contract + cost. ``fault.recovery_replay_ok`` is
    the crash-recovery drill boiled down to one gated bit: 1.0 iff the
    token streams across an injected crash+recover are exactly-once and
    bit-identical to an uninterrupted run. ``fault.storm_terminal_ratio``
    / ``fault.storm_leaked_blocks`` assert the typed-outcome and
    zero-leak contracts under a seeded fault storm. ``fault.overhead_-
    ratio`` is the median-step wall ratio of an engine carrying the
    fault-tolerance machinery (an attached — empty — FaultPlan, deadline
    scanning, watchdog) over one without, on a fault-free workload."""
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import (ShiftEngine, EngineConfig, FaultConfig,
                              PrefixConfig, Request)
    from repro.ft import DeliveryLog, FaultPlan, random_plan
    from repro.models import build_model

    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    n_new = 4 if smoke else 8

    def engine(faults=None, **kw):
        ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8, **kw)
        return ShiftEngine(m, m, params, params, ecfg,
                           policy=ThresholdPolicy(4), faults=faults)

    def reqs():
        return [Request(i, list(range(1, 11 + 2 * i)), max_new_tokens=n_new)
                for i in range(4)]

    # reference streams (uninterrupted)
    eng = engine()
    ref_reqs = reqs()
    for r in ref_reqs:
        eng.add_request(r)
    eng.run_until_idle(max_steps=400)
    ref = {r.rid: list(r.generated) for r in ref_reqs}

    # crash-recovery drill: crash mid-generation, recover, replay
    eng = engine(fault=FaultConfig(auto_snapshot_every=2))
    log = DeliveryLog()
    rs = reqs()
    for r in rs:
        eng.add_request(r)
    live = {r.rid: r for r in rs}
    for _ in range(5):
        eng.step()
        log.poll(live.values())
    eng2 = engine(fault=FaultConfig(auto_snapshot_every=2))
    replay_ok = 0.0
    try:
        eng2.recover(eng.retained_snapshots())
        live2 = {r.rid: r for r in eng2.queue}
        while eng2.queue or eng2.active:
            eng2.step()
            log.poll(live2.values())
        if all(log.delivered(rid) == ref[rid] for rid in live):
            replay_ok = 1.0
    except Exception:
        replay_ok = 0.0                 # divergence/SnapshotError -> 0
    rec("fault.recovery_replay_ok", replay_ok, "x")

    # seeded storm: typed outcomes + zero leak
    plan = random_plan(3, 40, p_alloc=0.15, p_forward=0.15, p_route=0.1)
    eng = engine(faults=plan, num_blocks=32,
                 prefix=PrefixConfig(enabled=True))
    rs = reqs()
    for r in rs:
        eng.add_request(r)
    eng.run_until_idle(max_steps=400)
    eng.drain(max_steps=400)
    acct = eng.block_accounting()
    rec("fault.storm_terminal_ratio",
        sum(1 for r in rs if r.finish_reason is not None) / len(rs), "x")
    rec("fault.storm_leaked_blocks", acct["used"] + acct["pinned"],
        "blocks")

    # bookkeeping overhead on the fault-free hot path
    def median_step(**kw):
        e = engine(**kw)
        for r in reqs():
            e.add_request(r)
        e.step()                        # warm-up: compile first shape
        ts = []
        while e.active or e.queue:
            t0 = time.perf_counter()
            e.step()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2] if ts else 0.0

    t_plain = median_step()
    t_ft = median_step(faults=FaultPlan([]),
                       fault=FaultConfig(deadline_s=1e9))
    rec("fault.overhead_ratio",
        (t_ft / t_plain) if t_plain > 0 else 1.0, "x")


def _spec_bench(rec, emit, smoke):
    """Speculative decoding contract + payoff on a repetitive workload
    (the agentic/code-edit trace shape the technique targets), boiled
    down to four gated numbers:

    * ``spec.replay_ok`` — 1.0 iff the spec-on streams are exactly-once
      under the DeliveryLog AND bitwise identical to a spec-off run
      (speculation is an execution optimization, never a sampling change).
    * ``spec.accepted_per_step`` — accepted draft tokens per verify
      iteration; > 1.0 means verify passes are paying for themselves.
    * ``spec.delivered_per_row`` — decode tokens delivered per decode
      row per iteration (1.0 = plain decode; the speedup numerator).
    * ``spec.rollback_blocks_leaked`` — blocks still mapped after
      drain(); any nonzero means rejected-draft rollback leaked KV."""
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import (ShiftEngine, EngineConfig, Request,
                              SpecConfig)
    from repro.ft import DeliveryLog
    from repro.models import build_model

    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))
    n_new = 24 if smoke else 48

    def run(k):
        ecfg = EngineConfig(max_slots=4, s_max=128, prefill_chunk=8,
                            spec=SpecConfig(k=k))
        eng = ShiftEngine(m, m, params, params, ecfg,
                          policy=ThresholdPolicy(4))
        # mildly repetitive prompts: the reduced greedy model settles
        # into short cycles the self-drafter predicts
        reqs = [Request(i, ([2, 3, 4] * 4)[:9 + i], max_new_tokens=n_new)
                for i in range(4)]
        log = DeliveryLog()
        for r in reqs:
            eng.add_request(r)
        while eng.queue or eng.active:
            eng.step()
            log.poll(reqs)             # incremental: multi-token suffixes
        return eng, reqs, log

    _, ref_reqs, _ = run(0)
    ref = {r.rid: list(r.generated) for r in ref_reqs}
    eng, rs, log = run(4)
    replay_ok = 1.0 if all(log.delivered(r.rid) == ref[r.rid]
                           for r in rs) else 0.0
    rec("spec.replay_ok", replay_ok, "x")
    ct = eng.obs.registry.counter_total
    verify_steps = sum(1 for s in eng.obs.step_records
                       if s.get("spec_proposed"))
    rows = sum(s["decode_tokens"] - s.get("spec_accepted", 0)
               for s in eng.obs.step_records)
    acc = ct("spec_accepted_total")
    emit(f"# spec: {ct('spec_proposed_total'):.0f} drafted, {acc:.0f} "
         f"accepted over {verify_steps} verify steps / {rows:.0f} rows")
    rec("spec.accepted_per_step", acc / max(verify_steps, 1), "x")
    rec("spec.delivered_per_row", (rows + acc) / max(rows, 1), "x")
    eng.drain(max_steps=400)
    acct = eng.block_accounting()
    rec("spec.rollback_blocks_leaked", acct["used"] + acct["pinned"],
        "blocks")


def _cluster_bench(rec, emit, smoke):
    """Cluster serving contract, boiled down to three gated numbers on a
    real 2-replica Router over reduced engines (single device, shared
    weights — all scheduling outputs, deterministic integers):

    * ``cluster.affinity_prefill_tokens_saved`` — prefill tokens the
      prefix-affinity router saves cluster-wide on a shared-prefix burst
      (the whole point of affinity: the shared span prefills ONCE across
      the cluster, not once per replica).
    * ``cluster.migrations`` — live migrations completed by the drill.
    * ``cluster.migration_replay_ok`` — 1.0 iff >= 1 migration happened
      AND the migrated request's delivered stream is exactly-once and
      bit-identical to an unmigrated single-engine run (DeliveryLog
      replay check included). Hard-gated at 1.0."""
    from repro.cluster import Router
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import (ShiftEngine, EngineConfig, PrefixConfig,
                              Request)
    from repro.models import build_model

    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg, dtype=jnp.float32)
    params = m.init_params(jax.random.key(0))

    def engine():
        ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8,
                            threshold=4, block_size=8,
                            prefix=PrefixConfig(enabled=True))
        return ShiftEngine(m, m, params, params, ecfg,
                           policy=ThresholdPolicy(4))

    n_new = 4 if smoke else 8
    # affinity A/B: 6 requests sharing a 24-token (3-block) prefix across
    # 2 replicas — affinity keeps them on one replica, so 5 of 6 reuse it
    shared = list(range(1, 25))
    router = Router([engine(), engine()], routing="affinity",
                    rebalance_every=0)
    for i in range(6):
        router.submit(Request(i, shared + [100 + 3 * i, 101 + 3 * i],
                              max_new_tokens=n_new))
    router.run_until_idle()
    rec("cluster.affinity_prefill_tokens_saved",
        router.counter_total("prefix_tokens_saved_total"), "tokens")

    # migration drill: decode a request mid-stream, move it to the other
    # replica, finish there; the delivered stream must match a bare
    # single-engine run bit-for-bit (exactly-once across the move)
    prompt = list(range(1, 17))
    ref_eng = engine()
    ref = Request(0, prompt, max_new_tokens=n_new + 4)
    ref_eng.add_request(ref)
    ref_eng.run_until_idle(max_steps=400)

    drill = Router([engine(), engine()], routing="least-loaded",
                   rebalance_every=0)
    drill.submit(Request(0, prompt, max_new_tokens=n_new + 4))
    replay_ok = 0.0
    try:
        for _ in range(200):
            drill.step()
            drill.poll()
            if len(drill.stream(0)) >= 2:
                break
        src = drill.owner(0)
        drill.migrate(0, 1 - src)
        drill.run_until_idle()
        if drill.migrations >= 1 \
                and drill.delivered(0) == list(ref.generated):
            replay_ok = 1.0
    except Exception:
        replay_ok = 0.0                 # ReplayDivergence/abort -> 0
    rec("cluster.migrations", drill.migrations, "iters")
    rec("cluster.migration_replay_ok", replay_ok, "x")


def _elastic_bench(rec, emit, smoke):
    """Elastic resharding contract, boiled down to three gated numbers on
    a real dp=2 paged engine (host mesh, reduced model — deterministic
    integers):

    * ``elastic.reshard_replay_ok`` — 1.0 iff a mid-decode grow (dp merge
      -> wider TP) plus a shrink back complete, every stream matches an
      uninterrupted dp=2 run bit for bit, and the drained ledger shows
      zero leaked blocks. Hard-gated at 1.0.
    * ``elastic.reshard_blocks_moved`` — KV blocks re-poured across the
      two swaps (deterministic placement; a change means the transfer
      plan changed).
    * ``elastic.reshard_pause_steps`` — extra engine iterations the
      resharded run needed over the reference (the swap happens BETWEEN
      iterations, so this should stay 0)."""
    from repro.configs import get_config
    from repro.core.policy import DEFAULT_SHIFT_THRESHOLD, ThresholdPolicy
    from repro.engine import ShiftEngine, EngineConfig, Request
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.parallel import Layout

    cfg = get_config("qwen3-8b").reduced()
    mesh_dp = make_test_mesh(data=2, sp=1, tp=1)
    mesh_tp = make_test_mesh(data=1, sp=1, tp=2)
    lay_dp = Layout.from_mesh(mesh_dp, dp=("data",), sp=("sp",), tp=("tp",))
    lay_tp = Layout.from_mesh(mesh_tp, dp=("data",), sp=("sp",), tp=("tp",))
    # enough decode runway that requests are still mid-stream at BOTH
    # swaps — a shrink with no holders would gate on an empty re-pour
    n_new = 8 if smoke else 12

    def engine(mesh, lay):
        mb = Model(cfg=cfg, lay=lay, mesh=mesh, dtype=jnp.float32)
        ms = Model(cfg=cfg, lay=lay.to_shift(), mesh=mesh,
                   dtype=jnp.float32)
        ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8,
                            block_size=8)
        return ShiftEngine(mb, ms, mb.init_params(jax.random.key(0)),
                           ms.init_params(jax.random.key(0)), ecfg,
                           policy=ThresholdPolicy(DEFAULT_SHIFT_THRESHOLD))

    def reqs():
        return [Request(i, list(range(1, 11 + 2 * i)),
                        max_new_tokens=n_new) for i in range(4)]

    def run_out(eng, rs):
        steps = 0
        for r in rs:
            eng.add_request(r)
        while eng.active or eng.queue:
            if not eng.step():
                break
            steps += 1
        return steps

    ref_eng, ref = engine(mesh_dp, lay_dp), reqs()
    ref_steps = run_out(ref_eng, ref)
    expect = {r.rid: list(r.generated) for r in ref}

    eng, rs = engine(mesh_dp, lay_dp), reqs()
    blocks_moved, replay_ok, drill_steps = 0, 0.0, 0
    try:
        for r in rs:
            eng.add_request(r)
        for _ in range(4):
            eng.step()
            drill_steps += 1
        rep = eng.reshard(lay_tp, mesh=mesh_tp)       # grow: dp merge
        for _ in range(3):
            eng.step()
            drill_steps += 1
        rep2 = eng.reshard(lay_dp, mesh=mesh_dp)      # shrink back
        blocks_moved = rep.blocks_moved + rep2.blocks_moved
        while eng.active or eng.queue:
            if not eng.step():
                break
            drill_steps += 1
        eng.drain(max_steps=400)
        led = eng.stats().blocks
        if ({r.rid: list(r.generated) for r in rs} == expect
                and led.used == 0 and led.pinned == 0):
            replay_ok = 1.0
    except Exception:
        replay_ok = 0.0                 # ReshardError/divergence -> 0
    rec("elastic.reshard_replay_ok", replay_ok, "x")
    rec("elastic.reshard_blocks_moved", blocks_moved, "blocks")
    rec("elastic.reshard_pause_steps", max(0, drill_steps - ref_steps),
        "iters")


def main(emit=print, smoke=False, out="BENCH_kernels.json"):
    entries = []

    def rec(name, value, unit):
        entries.append({"name": name, "value": float(value), "unit": unit})
        emit(f"kernel,{name},{value:.1f},{unit}")

    iters = 1 if smoke else 3
    _ref_benches(rec, iters)
    _ragged_vs_padded(rec, iters, smoke)
    _work_prop_attn(rec, emit, smoke)
    _mixed_vs_serialized(rec, smoke)
    _prefix_reuse(rec, smoke)
    _dp_paged_smoke(rec, emit)
    _obs_bench(rec, smoke)
    _fault_bench(rec, smoke)
    _spec_bench(rec, emit, smoke)
    _cluster_bench(rec, emit, smoke)
    _elastic_bench(rec, emit, smoke)
    if out:
        with open(out, "w") as f:
            json.dump({"smoke": smoke, "entries": entries}, f, indent=1)
        emit(f"# wrote {out} ({len(entries)} entries)")
    return entries


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / single iteration (CI)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    a = ap.parse_args()
    main(smoke=a.smoke, out=a.out)
