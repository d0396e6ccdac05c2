"""Arithmetic the metric readers share: percentiles, per-request token
gaps, and the model's operations and bytes (kept with the benchmark so that
no program change can move them).

A run record (``run``) is the dict ``bench/run.py`` builds after a window:
``seconds``, ``t_open``, ``t_close``, ``requests`` (tracked requests with
host-clock token times), ``steps`` (launched steps in the window:
start, end, config, rows, prefill tokens, decode tokens, attention pairs,
attended context tokens), ``trace_steps`` (the steps inside the traced
span), ``config_steps``, ``admitted_queue_s``, ``events_dropped``,
``compiles_in_window``, ``trace`` (``bench.trace_reduce`` output or None),
``spec``, ``peak``, ``chips``, ``groups``, ``setup_s``."""
from __future__ import annotations

import numpy as np

from bench.weights import dims

# step tuple fields
T0, T1, CONFIG, ROWS, PREFILL, DECODE, PAIRS, CTX = range(8)


def pct(values, q):
    """The q-th percentile, linear between order statistics (numpy's
    default); None for no values."""
    v = [x for x in values if x is not None]
    return float(np.percentile(v, q)) if v else None


def in_window(run, t):
    return run["t_open"] <= t < run["t_close"]


def sent_in_window(run):
    return [r for r in run["requests"] if r.phase in ("window", "burst")
            and in_window(run, r.sent)]


def ttft_s(run, r):
    """First-token time less due time; a request that never got one counts
    the whole wait to the end of the drain."""
    first = r.times[0] if r.times else run["drained_at"]
    return first - r.due


def tpot_s(run):
    """Each request's mean gap between its tokens emitted inside the
    window, for requests with two or more such tokens."""
    out = []
    for r in run["requests"]:
        ts = [t for t in r.times if in_window(run, t)]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def window_tokens(run):
    """Tokens delivered inside the window; the step running at its close
    counts the share of its tokens that its time inside the window is of
    its whole time (so the count does not jump by a step's tokens)."""
    n = sum(1 for r in run["requests"] for t in r.times if in_window(run, t))
    for s in run["steps"]:
        if s[T0] < run["t_close"] <= s[T1]:
            k = sum(1 for r in run["requests"] for t in r.times
                    if t == s[T1])
            n += k * (run["t_close"] - s[T0]) / (s[T1] - s[T0])
    return n


def model_flops(spec, steps):
    """Operations the model needs for the given steps: every token through
    every layer's projections and MLP (2 per multiply-add), the attention
    products (QK^T and PV: 4 * H * D per attended pair and layer), and the
    output head for one row per request per step."""
    m = dims(spec)
    d, H, K, D, F, L, V = (m[k] for k in "dHKDFLV")
    per_tok = 2 * L * (d * (H + 2 * K) * D + H * D * d + 3 * d * F)
    toks = sum(s[PREFILL] + s[DECODE] for s in steps)
    pairs = sum(s[PAIRS] for s in steps)
    rows = sum(s[ROWS] for s in steps)
    return per_tok * toks + 4 * H * D * L * pairs + 2 * d * V * rows


def attention_bytes(spec, steps, itemsize=2):
    """HBM bytes the attention algorithm must move: each row's keys and
    values once per layer (attended context tokens x KV width) and the
    fresh queries and outputs."""
    m = dims(spec)
    ctx = sum(s[CTX] for s in steps)
    toks = sum(s[PREFILL] + s[DECODE] for s in steps)
    kv = ctx * m["L"] * 2 * m["K"] * m["D"] * itemsize
    qo = toks * m["L"] * 2 * m["H"] * m["D"] * itemsize
    return kv + qo


def attention_flops(spec, steps):
    m = dims(spec)
    return 4 * m["H"] * m["D"] * m["L"] * sum(s[PAIRS] for s in steps)
