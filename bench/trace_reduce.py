"""Reduce a JAX profiler trace (``*.xplane.pb``) to device metrics.

Per device plane (``/device:TPU:<n>``), from its ``XLA Ops`` line:

- busy: the union of the operations' intervals inside the traced window
  (ops nest, e.g. a ``while`` around its body, so time is never summed);
- per-operation self time (duration less the ops nested in it), grouped
  by a short name: the HLO instruction name without its ``%`` and numeric
  suffix, or ``pallas:<kernel>`` for a Pallas kernel
  (``custom_call_target="tpu_custom_call"``);
- kernel time (Pallas custom calls) and collective time (all-reduce,
  all-gather, reduce-scatter, all-to-all, collective-permute);
- idle gaps: the window less the busy union, each labelled by the
  innermost harness span (``bench_*`` on the host plane) around its
  midpoint.

The traced window is the span from the first to the last harness
annotation named ``bench_window``, else the extent of the device ops."""
from __future__ import annotations

import collections
import glob
import os
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)*\s*=")


def short_name(full):
    """``%fusion.147 = bf16[...] fusion(...)`` -> ``fusion``."""
    if KERNEL_MARK in full:
        m = re.search(r'kernel_name="?([\w\-.]+)', full)
        return "pallas:" + (m.group(1) if m else "tpu_custom_call")
    m = _NAME.match(full)
    return m.group(1) if m else full.split(" ")[0][:64]


def find_xplanes(trace_dir):
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(events):
    """Self time of each (start, end, name) op, nested ops subtracted."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    self_t = [e[1] - e[0] for e in evs]
    stack = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return [(evs[i][2], max(self_t[i], 0.0)) for i in range(len(evs))]


def reduce(path_or_dir, span_prefix="bench_"):
    """Returns a dict of plain numbers (seconds) for the trace."""
    import jax
    paths = ([path_or_dir] if path_or_dir.endswith(".xplane.pb")
             else find_xplanes(path_or_dir))
    if not paths:
        return None
    devices, spans = [], []
    for p in paths:
        pd = jax.profiler.ProfileData.from_file(p)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                ops = []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
                if ops:
                    devices.append((plane.name, ops))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events
                              if e.name.startswith(span_prefix)]
    if not devices:
        return None
    win = [s for s in spans if s[2] == span_prefix + "window"]
    if win:
        lo, hi = min(s[0] for s in win), max(s[1] for s in win)
    else:
        lo = min(o[0] for _, ops in devices for o in ops)
        hi = max(o[1] for _, ops in devices for o in ops)
    inner = [s for s in spans if s[2] != span_prefix + "window"]
    per_dev = []
    op_self = collections.Counter()
    gap_by = collections.Counter()
    for name, ops in sorted(devices):
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in ops
               if e > lo and s < hi]
        busy = _union([(s, e) for s, e, _ in ops])
        busy_ns = sum(e - s for s, e in busy)
        kern = _union([(s, e) for s, e, n in ops if KERNEL_MARK in n])
        coll = _union([(s, e) for s, e, n in ops
                       if any(c in n.split("=")[0] or
                              f" {c}(" in n or f"{c}-start" in n
                              for c in COLLECTIVES)])
        selfs = _self_times(ops)
        for n, t in selfs:
            op_self[short_name(n)] += t / len(devices)
        gaps = []
        prev = lo
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            gaps.append((prev, hi))
        for s, e in gaps:
            mid = (s + e) / 2
            cover = [sp for sp in inner if sp[0] <= mid < sp[1]]
            label = (min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover
                     else "outside_bench_spans")
            gap_by[label] += (e - s) / len(devices)
        per_dev.append({"device": name, "busy_s": busy_ns * 1e-9,
                        "kernel_s": sum(e - s for s, e in kern) * 1e-9,
                        "collective_s": sum(e - s for s, e in coll) * 1e-9,
                        "n_ops": len(ops)})
    n = len(per_dev)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(d["busy_s"] for d in per_dev) / n,
            "kernel_s": sum(d["kernel_s"] for d in per_dev) / n,
            "collective_s": sum(d["collective_s"] for d in per_dev) / n,
            "devices": per_dev,
            "device_ops": [[k, v * 1e-9] for k, v in op_self.most_common(10)],
            "idle_gaps": [[k, v * 1e-9] for k, v in gap_by.most_common(10)]}
