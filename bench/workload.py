"""The one traffic generator. A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into a schedule.

Every seed serves the same work in another order: the multiset of request
sizes and of inter-arrival gaps is drawn once from a fixed base seed (so
the mix's rates and length quantiles hold exactly alike in every run), and
the run's seed permutes both and draws the prompt tokens. Runs of different
seeds then differ in order and content, not in how much they ask for.

Arrival kinds:

- ``poisson``: open loop at ``rate`` requests/s (exponential gaps);
  optional ``bursts``: every ``every_s`` seconds from ``first_s``, ``size``
  requests spread uniformly over ``spread_s`` seconds, with sizes of their
  own.
- ``backlog``: closed offline batch: ``requests`` requests, submitted so
  that ``queue`` wait beyond the engine's slots at all times.

Lengths: ``lognormal`` (``median``, ``sigma``, clipped to ``lo``..``hi``)
or ``uniform`` (integers ``lo``..``hi``).

Copied arithmetic: ``repro.sim.traces.bursty_trace`` (the steady stream and
the burst shape of the paper's Fig. 7), with heavy-tailed lengths where the
mix asks for them."""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import List

import numpy as np

BASE_SEED = 20250916          # fixed: the multiset of sizes and gaps


@dataclass
class Item:
    due: float                # seconds after the phase opens
    prompt_len: int
    out_len: int
    phase: str                # "warm" | "window" | "burst" | "backlog"
    prompt: List[int] = field(default_factory=list)


def load_mix(path):
    with open(path) as f:
        return json.load(f)


def _rng(seed, *tags):
    return np.random.default_rng([int(seed) % (1 << 64), *tags])


def lengths(spec, n, rng):
    """``n`` integer lengths of the distribution ``spec``."""
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def max_tokens(mix):
    """The longest request the mix can send (prompt + output)."""
    specs = [(mix["prompt"], mix["output"])]
    if "bursts" in mix["arrivals"]:
        b = mix["arrivals"]["bursts"]
        specs.append((b["prompt"], b["output"]))
    return max(int(p["hi"]) + int(o["hi"]) for p, o in specs)


def _sizes(mix_p, mix_o, n, tag, seed):
    base = _rng(BASE_SEED, tag)
    p = lengths(mix_p, n, base)
    o = lengths(mix_o, n, base)
    perm = _rng(seed, tag, 1).permutation(n)
    return p[perm], o[perm]


def _poisson(rate, span, tag, seed):
    """Arrival times in [0, span): base exponential gaps, seed-permuted."""
    base = _rng(BASE_SEED, tag, 2)
    gaps = []
    t = 0.0
    while True:
        g = float(base.exponential(1.0 / rate))
        if t + g >= span:
            break
        t += g
        gaps.append(g)
    gaps = np.asarray(gaps)[_rng(seed, tag, 3).permutation(len(gaps))]
    return np.cumsum(gaps)


def phase(mix, seed, name, span):
    """The items of one phase (``warm`` or ``window``) of an open-loop mix,
    due in [0, span)."""
    arr = mix["arrivals"]
    tag = {"warm": 1, "window": 2}[name]
    if arr["kind"] != "poisson":
        raise ValueError("phase() is for open-loop mixes")
    t = _poisson(float(arr["rate"]), span, tag, seed)
    p, o = _sizes(mix["prompt"], mix["output"], len(t), tag, seed)
    items = [Item(float(a), int(b), int(c), name) for a, b, c in zip(t, p, o)]
    b = arr.get("bursts")
    if b:
        starts = np.arange(float(b["first_s"]), span, float(b["every_s"]))
        n = len(starts) * int(b["size"])
        if n:
            base = _rng(BASE_SEED, tag, 4)
            offs = base.uniform(0, float(b["spread_s"]), n)
            bp, bo = _sizes(b["prompt"], b["output"], n, tag + 10, seed)
            due = np.repeat(starts, int(b["size"])) + offs
            items += [Item(float(a), int(x), int(y), "burst")
                      for a, x, y in zip(due, bp, bo)]
    items.sort(key=lambda it: it.due)
    return items


def backlog(mix, seed):
    arr = mix["arrivals"]
    n = int(arr["requests"])
    p, o = _sizes(mix["prompt"], mix["output"], n, 3, seed)
    return [Item(0.0, int(a), int(b), "backlog") for a, b in zip(p, o)]


def fill_prompts(items, vocab, seed, tag):
    """Draw each item's prompt tokens (ids 1 .. vocab-1) from the seed."""
    rng = _rng(seed, 100 + tag)
    for it in items:
        it.prompt = rng.integers(1, vocab, it.prompt_len).tolist()
    return items


def mix_path(bench_dir, name):
    return os.path.join(bench_dir, "traffic", f"{name}.json")
