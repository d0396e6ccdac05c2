#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one process serves several fixed
rates in turn, each for ``--seconds``, and reports for each whether the
backlog grows. The knee is the highest rate with no growing backlog.

    python bench/sweep.py --workload <open-loop cell> --seed 1 \\
        --rates 0.5,0.8,1.1 --seconds 60

Per rate: requests sent, first tokens and completions inside the segment,
requests waiting for a slot and in flight at the segment's start and end,
the TTFT and TPOT quantiles of the segment (host clock, as ``run.py``
measures them). The knee is the highest rate whose segment ends with no
request waiting for a slot and gave first tokens to 90% of what it sent;
``--set-rate f`` writes f x knee into the mix file. Needs the cell's
chips."""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--set-rate", type=float, default=0.0,
                    help="write this fraction of the knee into the mix "
                         "file's rate (0: leave the file alone)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness as H
    from bench import run as R
    from bench import stats
    from bench import workload as WL
    cell = H.load_cell(args.workload, ROOT)
    devs = R.require_chips(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = H.CompileClock()
    eng, info = H.build(cell, args.seed, devs)
    shapes = H.Shapes(eng)
    drv = H.Driver(eng, cell.spec["vocab_size"], args.seed)
    V = cell.spec["vocab_size"]
    rates = [float(r) for r in args.rates.split(",")]
    mix0 = dict(cell.mix, arrivals=dict(cell.mix["arrivals"], rate=rates[0]))
    warm = WL.fill_prompts(WL.phase(mix0, args.seed, "warm",
                                    float(cell.mix["warmup_s"])),
                           V, args.seed, 1)
    t = H.clock()
    drv.serve_open(warm, t, t + float(cell.mix["warmup_s"]))
    shapes.warm(shapes.closure(int(cell.mix["engine"]["chunk"]),
                               info["nmax"], info["slots"],
                               float(cell.mix.get("warm_recent", 1.0))))
    R.log(f"warm-up done at {H.clock() - T_START:.1f} s, "
          f"{len(shapes.seen)} buckets, {compiles.n} executables")
    table = []
    for k, rate in enumerate(rates):
        mix = dict(cell.mix, arrivals=dict(cell.mix["arrivals"], rate=rate))
        items = WL.fill_prompts(
            WL.phase(mix, args.seed + 1000 * (k + 1), "window", args.seconds),
            V, args.seed, 10 + k)
        before = {"waiting": sum(1 for r in eng.queue if r.slot is None),
                  "in_flight": len(eng.queue)}
        c0 = compiles.n
        t0 = H.clock()
        drv.serve_open(items, t0, t0 + args.seconds)
        t1 = H.clock()
        run = {"t_open": t0, "t_close": t0 + args.seconds,
               "drained_at": t1, "requests": list(drv.all.values())}
        sent = stats.sent_in_window(run)
        firsts = [stats.ttft_s(run, r) for r in sent if r.times
                  and r.times[0] < t1]
        done = sum(1 for r in drv.all.values()
                   if r.req.finish_time and t0 <= r.req.finish_time < t1)
        row = {"rate": rate, "sent": len(sent), "first_tokens": len(firsts),
               "finished": done, "start": before,
               "end": {"waiting": sum(1 for r in eng.queue
                                      if r.slot is None),
                       "in_flight": len(eng.queue)},
               "ttft_p50_ms": _ms(stats.pct(firsts, 50)),
               "ttft_p90_ms": _ms(stats.pct(firsts, 90)),
               "tpot_p50_ms": _ms(stats.pct(stats.tpot_s(
                   dict(run, t_close=t1)), 50)),
               "compiles": compiles.n - c0}
        table.append(row)
        R.log(json.dumps(row))
    ok = [r["rate"] for r in table if r["end"]["waiting"] == 0
          and r["first_tokens"] >= 0.9 * r["sent"]]
    knee = max(ok) if ok else None
    if knee and args.set_rate:
        path = WL.mix_path(os.path.join(ROOT, "bench"),
                           cell.entry["traffic"])
        mix = WL.load_mix(path)
        mix["arrivals"]["rate"] = round(args.set_rate * knee, 3)
        mix["arrivals"]["knee"] = knee
        with open(path, "w") as f:
            json.dump(mix, f, indent=2)
        R.log(f"rate set to {mix['arrivals']['rate']} req/s in {path}")
    print(json.dumps({"workload": cell.name, "seconds": args.seconds,
                      "knee": knee, "info": info, "sweep": table,
                      "device": {"kind": devs[0].device_kind,
                                 "count": len(devs)}}))


def _ms(x):
    return None if x is None else round(x * 1e3, 3)


if __name__ == "__main__":
    main()
