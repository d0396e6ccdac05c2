"""Serving entry point.

By default it runs the reduced float32 model, which is what CPU demos and
tests use: ``PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b``.
``--no-reduced`` serves the published widths in bfloat16 (``--num-layers``
cuts the depth, e.g. to one chip's share of a pipeline), and ``--sp/--tp``
build a (1, sp, tp) mesh over the local devices with the base (SP,TP) and
shift (TP) configs over the same KV pool. With ``--replicas N`` the same
stack runs as a cluster: N engine replicas behind the
``repro.cluster.Router`` (prefix-affinity routing, live KV migration under
skew, one merged obs dump)."""
from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import time

# must land before jax initializes so a CPU demo can run --dp > 1
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.cluster import Router
from repro.configs import get_config
from repro.core.policy import (DEFAULT_SHIFT_THRESHOLD, ThresholdPolicy,
                               AdaptivePolicy)
from repro.engine import (ShiftEngine, EngineConfig, FaultConfig,
                          PrefixConfig, Request, SpecConfig)
from repro.ft import random_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import build_model
from repro.models.model import Model
from repro.obs import build_report, format_report
from repro.parallel import Layout
from repro.sim.costmodel import CostModel


def _build_stack(arch: str, *, reduced=True, num_layers=0, mesh=None,
                 sp=1, tp=1, slots=8, s_max=256, chunk=64,
                 threshold=DEFAULT_SHIFT_THRESHOLD, adaptive=False,
                 paged=None, block_size=16, num_blocks=0, prefix_cache=False,
                 dp=1, dtype=None, deadline_s=None, max_queue=0,
                 shed_policy="reject-newest", auto_snapshot_every=0,
                 spec_k=0, spec_ngram=3):
    """Models + params + policy + EngineConfig, built once — replicas of a
    cluster share the stack (same weights: a migrated request decodes the
    same stream on any replica).

    ``reduced`` picks the toy CPU preset (float32 by default); otherwise the
    published widths run in bfloat16. ``num_layers`` > 0 cuts the depth.
    With ``dp*sp*tp > 1`` (and no explicit mesh) a (dp, sp, tp) mesh is
    built over the local devices; parameters and pools are created directly
    in their shardings on it."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if num_layers:
        if num_layers > cfg.num_layers:
            raise ValueError(f"num_layers={num_layers} exceeds {cfg.name}'s "
                             f"{cfg.num_layers}")
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    if dtype is None:
        dtype = jnp.float32 if reduced else jnp.bfloat16
    if mesh is None and dp * sp * tp > 1:
        if len(jax.devices()) < dp * sp * tp:
            raise ValueError(
                f"dp*sp*tp={dp * sp * tp} needs that many devices, have "
                f"{len(jax.devices())} (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before jax "
                "initializes for a CPU demo)")
        mesh = make_test_mesh(data=dp, sp=sp, tp=tp)
    if mesh is None:
        base = build_model(cfg, dtype=dtype)
        shift = base
    else:
        lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
        base = Model(cfg=cfg, lay=lay, mesh=mesh, dtype=dtype)
        shift = Model(cfg=cfg, lay=lay.to_shift(), mesh=mesh, dtype=dtype)
    params = base.init_params(jax.random.key(0))
    p_base = params
    p_shift = (params if mesh is None
               else shift.init_params(jax.random.key(0)))  # separate models
    policy = (AdaptivePolicy(CostModel(cfg), base.lay.sp, base.lay.tp)
              if adaptive
              else ThresholdPolicy(threshold))
    ecfg = EngineConfig(
        max_slots=slots, s_max=s_max, prefill_chunk=chunk,
        threshold=threshold, paged=paged, block_size=block_size,
        num_blocks=num_blocks,
        prefix=PrefixConfig(enabled=prefix_cache),
        spec=SpecConfig(k=spec_k, ngram_max=spec_ngram),
        fault=FaultConfig(deadline_s=deadline_s, max_queue=max_queue,
                          shed_policy=shed_policy,
                          auto_snapshot_every=auto_snapshot_every))
    return base, shift, p_base, p_shift, ecfg, policy


def build_engine(arch: str, *, faults=None, **kw):
    """One ShiftEngine over an optional (data, sp, tp) mesh. With dp > 1
    the engine pages per dp row — each row owns a private block pool and
    prefix index, and queued requests are routed to the row with the most
    free blocks."""
    base, shift, p_base, p_shift, ecfg, policy = _build_stack(arch, **kw)
    return ShiftEngine(base, shift, p_base, p_shift, ecfg, policy=policy,
                       faults=faults)


def build_cluster(arch: str, replicas: int, *, routing="affinity",
                  rebalance_every=8, faults=None, **kw) -> Router:
    """N engine replicas over ONE shared model/params stack, behind a
    Router. ``faults`` (a FaultPlan) applies to replica 0 only — the
    cluster demo's skew/migration drills need a healthy destination."""
    base, shift, p_base, p_shift, ecfg, policy = _build_stack(arch, **kw)
    engines = [ShiftEngine(base, shift, p_base, p_shift, ecfg,
                           policy=policy,
                           faults=faults if i == 0 else None)
               for i in range(replicas)]
    return Router(engines, routing=routing, rebalance_every=rebalance_every)


def _is_idle(client) -> bool:
    if hasattr(client, "engines"):                 # cluster Router
        return all(st.queue_depth == 0 and st.active == 0
                   for st in (e.stats() for e in client.engines))
    return not client.queue and not client.active  # bare engine


def serve_loop(client, *, refresh_s=0.0, prom_path=None, max_steps=10000,
               now=time.monotonic):
    """Drive ``client`` to idle like ``run_until_idle``, but with a LIVE
    metrics scrape surface: with ``refresh_s`` > 0 and a ``prom_path``,
    the Prometheus text exposition is rewritten every ``refresh_s``
    seconds of serving (and once at exit), so a file-based scraper (e.g.
    node_exporter's textfile collector) sees fresh counters while
    requests are still in flight instead of one post-run artifact.
    Returns the number of refreshes written; ``now`` is injectable so
    tests can drive the refresh clock deterministically."""
    writer = getattr(client, "write_prometheus", None) \
        or client.obs.write_prometheus
    if not (refresh_s and prom_path):
        client.run_until_idle(max_steps)
        return 0
    poll = getattr(client, "poll", None)
    n_refresh = 0
    last = now()
    for _ in range(max_steps):
        if poll is not None:
            poll()
        progressed = client.step()
        t = now()
        if t - last >= refresh_s:
            writer(prom_path)
            last = t
            n_refresh += 1
        if not progressed and _is_idle(client):
            break
    if poll is not None:
        poll()
    writer(prom_path)                  # final state is always current
    return n_refresh + 1


def _print_engine_summary(eng, label=""):
    st = eng.stats()
    print(f"{label}configs used: base={st.config_counts['base']} "
          f"shift={st.config_counts['shift']}")
    if st.paged:
        print(f"{label}paged cache: {st.dp} dp row(s) x "
              f"{st.blocks_per_row} blocks x {st.block_size} tokens, "
              f"{st.preemptions} preemptions, {st.free_blocks} free at exit")
        for r, free in enumerate(st.blocks.free_per_row):
            print(f"{label}  row {r}: {free} free blocks")
        p = st.prefix
        if eng.cfg.prefix.enabled:
            print(f"{label}prefix cache: {p.entries} cached blocks, "
                  f"{p.hits} hits / {p.misses} misses, "
                  f"{p.tokens_saved} prefill tokens saved, "
                  f"{p.evictions} evictions, {p.cow_copies} COW copies")
    else:
        # the dense fallback is loud: say WHY paging is off (also recorded
        # in prefix stats / step records)
        print(f"{label}dense cache fallback: {st.paged_disabled_reason}")
    if eng.cfg.spec.k:
        if eng.spec_disabled_reason:
            print(f"{label}spec decode DISABLED: {eng.spec_disabled_reason}")
        else:
            prop = int(eng.obs.registry.counter_total("spec_proposed_total"))
            acc = int(eng.obs.registry.counter_total("spec_accepted_total"))
            rate = f" ({acc / prop:.0%} acceptance)" if prop else ""
            print(f"{label}spec decode: k={eng.cfg.spec.k}, {prop} drafted, "
                  f"{acc} accepted{rate}")


def _reshard_demo(arch: str, *, requests=4, max_new=8):
    """Elastic resharding demo: a dp=2 engine reshards mid-decode to the
    merged pure-TP layout (dp merge -> wider TP) and back, and every
    stream still matches an uninterrupted reference run bit for bit."""
    if len(jax.devices()) < 2:
        raise ValueError(
            "--reshard-demo needs >= 2 devices (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "jax initializes for a CPU demo)")
    cfg = get_config(arch).reduced()
    mesh_dp = make_test_mesh(data=2, sp=1, tp=1)
    mesh_tp = make_test_mesh(data=1, sp=1, tp=2)
    lay_dp = Layout.from_mesh(mesh_dp, dp=("data",), sp=("sp",), tp=("tp",))
    lay_tp = Layout.from_mesh(mesh_tp, dp=("data",), sp=("sp",), tp=("tp",))
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8, block_size=8)
    policy = ThresholdPolicy(DEFAULT_SHIFT_THRESHOLD)

    def build(mesh, lay):
        base = Model(cfg=cfg, lay=lay, mesh=mesh, dtype=jnp.float32)
        shift = Model(cfg=cfg, lay=lay.to_shift(), mesh=mesh,
                      dtype=jnp.float32)
        return ShiftEngine(base, shift, base.init_params(jax.random.key(0)),
                           shift.init_params(jax.random.key(0)), ecfg,
                           policy=policy)

    def reqs():
        return [Request(i, list(range(1, 11 + 2 * i)), max_new_tokens=max_new)
                for i in range(requests)]

    print(f"reference: static {lay_dp.describe()} run")
    ref = build(mesh_dp, lay_dp)
    ref_reqs = reqs()
    for r in ref_reqs:
        ref.submit(r)
    ref.run_until_idle()
    expect = {r.rid: list(r.generated) for r in ref_reqs}

    eng = build(mesh_dp, lay_dp)
    rs = reqs()
    for r in rs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    print(f"resharding mid-decode: {lay_dp.describe()} -> "
          f"{lay_tp.describe()} (dp merge, wider TP)")
    rep = eng.reshard(lay_tp, mesh=mesh_tp)
    print(f"  {rep.delta.kind}: {rep.moved_requests} requests, "
          f"{rep.blocks_moved} KV blocks re-poured")
    for _ in range(3):
        eng.step()
    print(f"resharding back: {lay_tp.describe()} -> {lay_dp.describe()}")
    rep2 = eng.reshard(lay_dp, mesh=mesh_dp)
    print(f"  {rep2.delta.kind}: {rep2.moved_requests} requests, "
          f"{rep2.blocks_moved} KV blocks re-poured")
    eng.run_until_idle()
    got = {r.rid: list(r.generated) for r in rs}
    ok = got == expect
    for rid in sorted(got):
        print(f"req {rid}: {len(got[rid])} tokens, "
              f"bit-identical={got[rid] == expect.get(rid)}")
    eng.drain()
    led = eng.stats().blocks
    print(f"drained: used={led.used} pinned={led.pinned} blocks")
    counters = {c["name"]: c["value"]
                for c in eng.obs.dump()["metrics"]["counters"]}
    print(f"obs: reshards_total={counters.get('reshards_total', 0)} "
          f"reshard_blocks_moved_total="
          f"{counters.get('reshard_blocks_moved_total', 0)}")
    print("PASS: streams bit-identical across grow+shrink" if ok
          else "FAIL: streams diverged after reshard")
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="toy float32 preset (default); --no-reduced serves "
                         "the published widths in bfloat16")
    ap.add_argument("--num-layers", type=int, default=0,
                    help="cut the depth to N layers (0 = all)")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel degree of the base config")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree of the base config; the "
                         "shift config runs pure TP over sp*tp devices")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV block size (tokens)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="physical KV blocks; 0 = no memory pressure. Small "
                         "values force admission control + preemption")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="hash-indexed prefix reuse + copy-on-write on the "
                         "paged pool")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared 'system prompt' tokens "
                         "to every request (demonstrates prefix reuse)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel rows: ONE engine pages per-row "
                         "block pools over a dp×1×1 mesh (CPU demo needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the cluster Router "
                         "(prefix-affinity routing + live KV migration); "
                         "1 = a bare engine, no Router")
    ap.add_argument("--routing", default="affinity",
                    choices=["affinity", "round-robin", "least-loaded"],
                    help="Router policy for --replicas > 1")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: up to K self-drafted tokens "
                         "verified per decode row per iteration (0 = off). "
                         "Greedy streams are bitwise identical either way")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest suffix n-gram the self-drafter matches")
    ap.add_argument("--metrics-out", metavar="PATH",
                    help="write the observability dump as JSON to PATH and "
                         "the Prometheus text exposition next to it "
                         "(PATH with a .prom extension)")
    ap.add_argument("--metrics-refresh-s", type=float, default=0.0,
                    help="with --metrics-out: rewrite the .prom exposition "
                         "every S seconds WHILE serving (live scrape "
                         "surface), not just once at exit")
    ap.add_argument("--profile-dir", metavar="DIR",
                    help="record a JAX profiler trace of the serve loop in "
                         "DIR: the engine's step and phase spans "
                         "(engine.*) beside the device ops on one clock, "
                         "as an XPlane and a Perfetto trace "
                         "(perfetto_trace.json.gz, ui.perfetto.dev)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds past arrival); "
                         "expired requests finish with reason=timeout")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on requests waiting for a slot; 0 = "
                         "unbounded. Overflow is shed per --shed-policy")
    ap.add_argument("--shed-policy", default="reject-newest",
                    choices=["reject-newest", "evict-longest-queued"])
    ap.add_argument("--auto-snapshot-every", type=int, default=0,
                    help="checkpoint engine state every N steps into the "
                         "retained snapshot ring (crash recovery)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="inject a seeded deterministic fault storm "
                         "(see repro.ft.random_plan)")
    ap.add_argument("--fault-steps", type=int, default=64,
                    help="steps covered by the seeded fault storm")
    ap.add_argument("--p-fault", type=float, default=0.05,
                    help="per-step per-seam fault probability for the "
                         "seeded storm (alloc/forward/route seams)")
    ap.add_argument("--reshard-demo", action="store_true",
                    help="elastic resharding demo: a dp=2 engine swaps its "
                         "Deployment to merged pure-TP mid-decode and back; "
                         "streams must match a static reference bit for bit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.reshard_demo:
        raise SystemExit(_reshard_demo(args.arch, requests=args.requests,
                                       max_new=args.max_new))

    faults = None
    if args.fault_seed is not None:
        faults = random_plan(args.fault_seed, args.fault_steps,
                             p_alloc=args.p_fault, p_forward=args.p_fault,
                             p_route=args.p_fault, dp=args.dp)
        print(f"fault plan: seed={args.fault_seed} "
              f"{len(faults)} faults over {args.fault_steps} steps")
    if args.metrics_refresh_s and not args.metrics_out:
        ap.error("--metrics-refresh-s requires --metrics-out")
    cfg = get_config(args.arch)
    cfg = cfg.reduced() if args.reduced else cfg
    depth = args.num_layers or cfg.num_layers
    print(f"model: {cfg.name}, "
          f"{'reduced float32 preset' if args.reduced else 'published widths, bfloat16'}, "
          f"{depth} of {cfg.num_layers} layers"
          f"{' (depth cut)' if depth < cfg.num_layers else ''}, "
          f"mesh dp={args.dp} sp={args.sp} tp={args.tp}")
    kw = dict(reduced=args.reduced, num_layers=args.num_layers,
              sp=args.sp, tp=args.tp,
              adaptive=args.adaptive, block_size=args.block_size,
              num_blocks=args.num_blocks, prefix_cache=args.prefix_cache,
              dp=args.dp, deadline_s=args.deadline_s,
              max_queue=args.max_queue, shed_policy=args.shed_policy,
              auto_snapshot_every=args.auto_snapshot_every,
              spec_k=args.spec_k, spec_ngram=args.spec_ngram)
    if args.replicas > 1:
        client = build_cluster(args.arch, args.replicas,
                               routing=args.routing, faults=faults, **kw)
        print(f"cluster: {args.replicas} replicas, routing={args.routing}")
    else:
        client = build_engine(args.arch, faults=faults, **kw)
    system = list(range(1000, 1000 + args.shared_prefix))
    reqs = [Request(i, system + list(range(1, 20 + 3 * i)),
                    max_new_tokens=args.max_new, arrival=time.monotonic())
            for i in range(args.requests)]
    for r in reqs:
        client.submit(r)

    # graceful shutdown: SIGTERM (and Ctrl-C) drains in-flight decodes and
    # sheds the queue, so every request still reaches a typed terminal
    # outcome and the metrics/trace artifacts are flushed below
    def _sigterm(signum, frame):
        raise KeyboardInterrupt
    try:
        signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass                          # not on the main thread (tests)

    prom = (os.path.splitext(args.metrics_out)[0] + ".prom"
            if args.metrics_out else None)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir,
                                 create_perfetto_trace=True)
    t0 = time.monotonic()
    try:
        n_refresh = serve_loop(client, refresh_s=args.metrics_refresh_s,
                               prom_path=prom)
        if n_refresh:
            print(f"live metrics: {prom} refreshed {n_refresh}x "
                  f"(every {args.metrics_refresh_s}s)")
    except KeyboardInterrupt:
        print("\ninterrupt: draining in-flight requests, shedding queue...")
        client.drain()
        st = client.stats()
        ledgers = ([r.blocks for r in st.replicas]
                   if args.replicas > 1 else [st.blocks])
        for i, led in enumerate(ledgers):
            print(f"drained: replica {i}: used={led.used} "
                  f"pinned={led.pinned} blocks after shutdown")
    dt = time.monotonic() - t0
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"profiler trace written under {args.profile_dir}")
    for r in reqs:
        # read through the facade: after a live migration the submitted
        # Request object is stale (the request lives on at its new replica)
        live = client.request(r.rid) or r
        ttft = (live.first_token_time - live.arrival) \
            if live.first_token_time else -1
        out = client.stream(r.rid)
        print(f"req {r.rid}: {len(out)} tokens, "
              f"reason={live.finish_reason}, ttft={ttft*1e3:.0f}ms, "
              f"out={out[:8]}...")
    n_tok = sum(len(client.stream(r.rid)) for r in reqs)
    print(f"{n_tok} tokens in {dt:.2f}s")
    if args.replicas > 1:
        cs = client.stats()
        for i, eng in enumerate(client.engines):
            _print_engine_summary(eng, label=f"[replica {i}] ")
        print(f"cluster: {cs.migrations} migrations "
              f"({cs.migrated_blocks} KV blocks moved), "
              f"routing={cs.routing}, {cs.steps} router steps")
    else:
        # totals, not config_trace.count(): the trace is a rolling window
        _print_engine_summary(client)

    dump = client.dump() if args.replicas > 1 else client.obs.dump()
    print(format_report(build_report(dump)))
    if args.metrics_out:
        if args.replicas > 1:
            client.write_json(args.metrics_out)
            client.write_prometheus(prom)
        else:
            client.obs.write_json(args.metrics_out)
            client.obs.write_prometheus(prom)
        print(f"metrics written: {args.metrics_out} (JSON), {prom} "
              "(Prometheus text)")


if __name__ == "__main__":
    main()
