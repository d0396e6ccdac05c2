#!/usr/bin/env python3
"""Served-path benchmark, one cell per call:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the chips the cell names
(a TPU; no CPU fallback). One process: it builds the served path with
seed-drawn weights, warms up on the cell's own traffic, measures for
``--seconds``, drains, checks a sample of the served tokens against the
float32 reference, and prints one JSON line last on standard output. With
``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of part of the window gives its per-layer
metrics and a ``breakdown``. The numbers compared for ``correct`` close
standard error and the line (``checks``).

``--control 1`` runs the check's control instead: at every served position
of the same sample the token that the reference in float8 matrix products
puts first takes the served token's place, and goes through the same
comparison (so a sound limit reads ``correct`` false). The program's own
mean gap on that sample is printed beside it. The benchmark's runs leave it
off; ``bench/repeat.py --control 1`` reads it over seeds."""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
DRAIN_S = 60.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_reader(name, bench_dir=BENCH):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics, run, bench_dir=BENCH):
    out = {}
    for m in metrics:
        v = load_reader(m["name"], bench_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def require_chips(n):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness as H
    cell = H.load_cell(args.workload, ROOT)
    devs = require_chips(cell.chips)
    result = run_cell(cell, devs, args.seed, args.seconds, bool(args.trace),
                      bool(args.control))
    checks = result["checks"]
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result))


def run_cell(cell, devs, seed, seconds, trace, control=False):
    """One run of ``cell``; returns the result line as a dict. With
    ``control`` the control's tokens are compared in the served tokens'
    place."""
    import jax
    import numpy as np
    from bench import check as C
    from bench import harness as H
    from bench import stats
    from bench import trace_reduce
    from bench import workload as WL
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = H.CompileClock()
    mix, spec = cell.mix, cell.spec
    eng, info = H.build(cell, seed, devs)
    log(f"built {cell.name}: {json.dumps(info)} "
        f"({H.clock() - T_START:.1f} s, {compiles.n} executables)")
    shapes = H.Shapes(eng)
    drv = H.Driver(eng, spec["vocab_size"], seed, annotate=trace)
    V = spec["vocab_size"]
    arr = mix["arrivals"]
    chunk = int(mix["engine"]["chunk"])
    if arr["kind"] == "poisson":
        warm = WL.fill_prompts(
            WL.phase(mix, seed, "warm", float(mix["warmup_s"])), V, seed, 1)
        items = WL.fill_prompts(WL.phase(mix, seed, "window", seconds),
                                V, seed, 2)
        t = H.clock()
        drv.serve_open(warm, t, t + float(mix["warmup_s"]))
        nxt = 0
    else:
        items = WL.fill_prompts(WL.backlog(mix, seed), V, seed, 3)
        nxt = drv.serve_backlog(items, 0, int(arr["queue"]),
                                lambda: H.slots_prefilled(eng, info["slots"]))
        for _ in range(int(mix.get("warm_steps", 0))):
            drv.step()                  # the window's own (decode) buckets
    reached = len(shapes.launches)
    shapes.warm(shapes.closure(chunk, info["nmax"], info["slots"],
                               float(mix.get("warm_recent", 1.0))))
    log(f"warm-up: {reached} launches, {len(shapes.seen)} shape buckets "
        f"run, {compiles.n} executables in {compiles.seconds:.1f} s")

    # ------------------------------------------------------------ window
    t_open = H.clock()
    setup_s = t_open - T_START
    c0, steps0 = compiles.n, len(drv.steps)
    cfg0 = dict(eng.config_counts)
    ev0 = len(eng.obs.events.events)
    t_close = t_open + seconds
    tr = mix.get("trace", {})
    t_tr0 = t_open + min(float(tr.get("offset_s", 0.0)), seconds)
    t_tr1 = min(t_tr0 + float(tr.get("seconds", seconds)), t_close)
    state = {"next": nxt}

    def serve_until(t_end):
        if arr["kind"] == "poisson":
            state["next"] = drv.serve_open(items, t_open, t_end,
                                           state["next"])
        else:
            state["next"] = drv.serve_backlog(
                items, state["next"], int(arr["queue"]),
                lambda: H.clock() >= t_end)

    trace_dir, tr_idx = None, (0, 0)
    if trace:
        serve_until(t_tr0)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=H._trace_options())
        i0 = len(drv.steps)
        with jax.profiler.TraceAnnotation("bench_window"):
            serve_until(t_tr1)
        jax.profiler.stop_trace()
        tr_idx = (i0, len(drv.steps))
    serve_until(t_close)
    n_window_compiles = compiles.n - c0
    cfg1 = dict(eng.config_counts)
    admitted = [e["queue_s"] for e in eng.obs.events.events[ev0:]
                if e["kind"] == "admitted" and t_open <= e["ts"] < t_close]
    events_dropped = eng.obs.events.dropped

    # ---------------------------------------------- drain first tokens
    waiting = [t for t in drv.all.values() if t.phase in ("window", "burst")
               and t_open <= t.sent < t_close]
    t_stop = H.clock() + DRAIN_S
    while any(not t.times and t.req.finish_reason is None for t in waiting) \
            and H.clock() < t_stop and drv.busy():
        drv.step()
    drained_at = H.clock()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    steps = [s for s in drv.steps[steps0:] if t_open <= s[0] < t_close]
    run = {"seconds": seconds, "t_open": t_open,
           "t_close": t_close, "drained_at": drained_at,
           "requests": list(drv.all.values()), "steps": steps,
           "trace_steps": drv.steps[tr_idx[0]:tr_idx[1]] if trace else [],
           "config_steps": {k: cfg1[k] - cfg0.get(k, 0) for k in cfg1},
           "admitted_queue_s": admitted, "events_dropped": events_dropped,
           "compiles_in_window": n_window_compiles, "trace": None,
           "spec": spec, "chips": cell.chips,
           "groups": int(spec["mesh"]["sp"]) * int(spec["mesh"]["tp"]),
           "peak": _peaks(devs[0].device_kind), "setup_s": setup_s}
    late = np.asarray(drv.lateness[-len(items):] if items else [0.0])
    log(f"window: {seconds} s, {len(steps)} steps, {len(admitted)} "
        f"admissions, {n_window_compiles} executables built inside; "
        f"generator late p50 {np.median(late) * 1e3:.3f} ms max "
        f"{late.max() * 1e3:.3f} ms (not a metric); drained in "
        f"{drained_at - t_close:.1f} s; peak {peak} B")
    if n_window_compiles:
        log(f"shapes reached: {sorted(shapes.seen)}")
    sent = stats.sent_in_window(run)
    if arr["kind"] == "poisson":
        attempted = sent
    else:
        attempted = [t for t in drv.all.values()
                     if any(stats.in_window(run, x) for x in t.times)]
    failed = sum(1 for t in attempted
                 if (t.req.finish_reason is not None
                     and str(t.req.finish_reason) != "ok")
                 or (t in sent and not t.times))
    chosen = C.sample(list(drv.all.values()), seed,
                      int(cell.local["check_tokens"]),
                      int(cell.local["check_requests"]))
    if trace and trace_dir:
        run["trace"] = trace_reduce.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = read_metrics(cell.layer if trace else cell.e2e, run)

    # ------------------------------------------------- correctness
    drv.eng = shapes.eng = None
    del eng, shapes
    gc.collect()
    t = H.clock()
    g, served = np.asarray([np.inf]), None
    if chosen and control:
        served, g = C.gaps(spec, seed, chosen, control=True)
    elif chosen:
        g = C.gaps(spec, seed, chosen)
    gap = float(np.mean(g))
    log(f"reference: {len(chosen)} requests, "
        f"{sum(len(c.req.generated) for c in chosen)} served tokens "
        f"(base {sum(c.by_config['base'] for c in chosen)}, shift "
        f"{sum(c.by_config['shift'] for c in chosen)}) in "
        f"{H.clock() - t:.1f} s; "
        + ("control tokens in the served tokens' place; " if control else "")
        + f"widest gap {float(np.max(g))!r}, share of tokens off the "
        f"reference's best {float(np.mean(g > 0))!r}")
    limit = float(cell.local["mean_gap_limit"])
    checks = {"mean_logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = gap <= limit and failed == 0
    if served is not None:
        log(f"program's own mean gap on this sample: "
            f"{float(np.mean(served))!r}")
        checks["program_mean_logit_gap"] = {"value": float(np.mean(served)),
                                            "limit": limit}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        red = run["trace"] or {}
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", 0.0)
        result["breakdown"] = {"device_ops": red.get("device_ops", []),
                               "idle_gaps": red.get("idle_gaps", [])}
    result["checks"] = checks
    return result


def _peaks(kind):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


if __name__ == "__main__":
    main()
