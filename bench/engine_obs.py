"""What the served step's own spans, counters and names give the benchmark.

``ShiftEngine.step`` runs as the profiler span ``engine.step`` (its index
in the ``step_num`` stat) around one span per phase: ``engine.admit``,
``engine.plan``, ``engine.inputs``, ``engine.launch``, ``engine.wait``,
``engine.apply``. Its step record carries ``host_s`` (host seconds per
phase), ``bucket`` (the launched [rows, chunk, table width]),
``kv_blocks`` (blocks covering the launched rows' contexts after the
step's write) and ``compiles`` (executables built during the step). The
mixed step programs are the modules ``jit_step_base`` and
``jit_step_shift``, and the attention kernel's custom call is named
``paged_ragged_attention``.

``reduce`` reads a profiler trace (``*.xplane.pb``) inside the traced
window (the ``bench_window`` span, else the extent of the device ops):

- ``modules``: per module name, ``(<hash>)`` stripped, the launches that
  overlap the window and their summed device seconds, from each device
  plane's ``XLA Modules`` line (mean over devices);
- ``engine_spans``: per ``engine.*`` span name, the count that overlap
  the window and their summed host seconds;
- ``idle_gaps``: the window less the union of the device ops, split at
  the ``bench_`` and ``engine.`` span boundaries inside it, each piece
  labelled by the innermost such span around it (``outside_spans`` where
  there is none). A gap that runs from one step's ``engine.wait`` into
  the next step's phases is thus shared out by phase.

The readers take a run record (``bench.stats``) that also holds
``step_records``, the engine's records of the window's launched steps in
the order of ``steps``, and a ``trace`` holding ``modules``. A run without
these (a program that lacks the spans or counters) reads None."""
from __future__ import annotations

import collections
import re

from bench import stats
from bench.trace_reduce import _union, find_xplanes

PREFIXES = ("bench_", "engine.")
_HASH = re.compile(r"\(\d+\)$")


def reduce(path_or_dir):
    import jax
    paths = ([path_or_dir] if path_or_dir.endswith(".xplane.pb")
             else find_xplanes(path_or_dir))
    devices, spans = [], []
    for p in paths:
        for plane in jax.profiler.ProfileData.from_file(p).planes:
            if plane.name.startswith("/device:TPU:"):
                ops, mods = [], []
                for line in plane.lines:
                    evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        ops += evs
                    elif line.name == "XLA Modules":
                        mods += evs
                devices.append((ops, mods))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events
                              if e.name.startswith(PREFIXES)]
    win = [s for s in spans if s[2] == "bench_window"]
    every = [o for ops, _ in devices for o in ops]
    if win:
        lo, hi = min(s[0] for s in win), max(s[1] for s in win)
    elif every:
        lo, hi = min(o[0] for o in every), max(o[1] for o in every)
    else:
        lo, hi = float("-inf"), float("inf")
    inside = lambda s: s[1] > lo and s[0] < hi  # noqa: E731
    n = max(len(devices), 1)
    modules = collections.defaultdict(lambda: {"launches": 0, "seconds": 0.0})
    gaps = collections.Counter()
    inner = [s for s in spans if s[2] != "bench_window"]
    for ops, mods in devices:
        for s, e, name in filter(inside, mods):
            m = modules[_HASH.sub("", name)]
            m["launches"] += 1 / n
            m["seconds"] += (e - s) * 1e-9 / n
        busy = _union([(max(s, lo), min(e, hi)) for s, e, _ in ops
                       if e > lo and s < hi])
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                cuts = sorted({prev, s} | {t for sp in inner for t in sp[:2]
                                           if prev < t < s})
                for u, v in zip(cuts, cuts[1:]):
                    gaps[_innermost(inner, (u + v) / 2)] += (v - u) * 1e-9 / n
            prev = max(prev, e)
    engine = collections.defaultdict(lambda: {"count": 0, "seconds": 0.0})
    for s, e, name in filter(inside, spans):
        if name.startswith("engine."):
            engine[name]["count"] += 1
            engine[name]["seconds"] += (e - s) * 1e-9
    return {"modules": dict(modules), "engine_spans": dict(engine),
            "idle_gaps": [[k, v] for k, v in gaps.most_common(10)]}


def _innermost(spans, t):
    cover = [sp for sp in spans if sp[0] <= t < sp[1]]
    return (min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover
            else "outside_spans")


# ---------------------------------------------------------------- readers
def window_records(run, *fields):
    """The window's launched steps' records that carry every ``field``."""
    return [r for r in run.get("step_records") or []
            if all(f in r for f in fields)]


def traced_records(run, *fields):
    """Those of the steps in the traced slice (by their host start)."""
    ts = run.get("trace_steps") or []
    if not ts:
        return []
    lo, hi = ts[0][stats.T0], ts[-1][stats.T1]
    return [r for r in window_records(run, *fields)
            if lo <= r["t_start"] < hi]


def device_step_ms(run):
    """Mean device duration of the ``jit_step_*`` launches in the trace."""
    mods = [m for name, m in ((run.get("trace") or {}).get("modules")
                              or {}).items() if name.startswith("jit_step_")]
    n = sum(m["launches"] for m in mods)
    return 1e3 * sum(m["seconds"] for m in mods) / n if n else None


def host_step_ms(run, records=None):
    """Mean over the traced slice's launched steps of the host phases'
    seconds less ``wait`` (the host blocked on the device)."""
    recs = traced_records(run, "host_s") if records is None else records
    if not recs:
        return None
    return 1e3 * sum(sum(r["host_s"].values()) - r["host_s"].get("wait", 0.0)
                     for r in recs) / len(recs)


def kv_grid_used_pct(run):
    """KV blocks the launched rows' contexts cover over the blocks the
    attention grid visits (rows x table width), over the window."""
    recs = window_records(run, "kv_blocks", "bucket")
    grid = sum(r["bucket"][0] * r["bucket"][2] for r in recs)
    return 100.0 * sum(r["kv_blocks"] for r in recs) / grid if grid else None


def compiles_in_window(run):
    """Executables the window's launched steps built."""
    recs = window_records(run, "compiles")
    return sum(r["compiles"] for r in recs) if recs else None
