"""The traffic generator: the same seed gives the same schedule, another
seed another order of the same work; rates and length quantiles hold; the
harness stamps each request's arrival at its due time."""
import json
import os

import numpy as np
import pytest

from bench import workload as WL

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


BURSTY = {"engine": {"slots": 16, "chunk": 256, "block_size": 16},
          "arrivals": {"kind": "poisson", "rate": 0.3,
                       "bursts": {"first_s": 5, "every_s": 15, "size": 16,
                                  "spread_s": 1.0,
                                  "prompt": {"dist": "uniform", "lo": 1024,
                                             "hi": 4096},
                                  "output": {"dist": "uniform", "lo": 128,
                                             "hi": 512}}},
          "prompt": {"dist": "uniform", "lo": 256, "hi": 2048},
          "output": {"dist": "uniform", "lo": 64, "hi": 256}}


CHAT = {"engine": {"slots": 64, "chunk": 256, "block_size": 16},
        "arrivals": {"kind": "poisson", "rate": 0.64},
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "lo": 32, "hi": 4096},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "lo": 8, "hi": 1024}}


def mix(name):
    if name in ("chat", "bursty"):      # open-loop shapes with no cell yet
        return {"chat": CHAT, "bursty": BURSTY}[name]
    return WL.load_mix(WL.mix_path(BENCH, name))


def sched(m, seed, span=300.0):
    items = WL.phase(m, seed, "window", span)
    WL.fill_prompts(items, 1000, seed, 2)
    return items


@pytest.mark.parametrize("name", ["chat", "bursty"])
def test_same_seed_same_schedule(name):
    a, b = sched(mix(name), 2**33 + 1), sched(mix(name), 2**33 + 1)
    assert [(i.due, i.prompt_len, i.out_len, i.prompt) for i in a] == \
        [(i.due, i.prompt_len, i.out_len, i.prompt) for i in b]


@pytest.mark.parametrize("name", ["chat", "bursty"])
def test_other_seed_other_order_same_work(name):
    a, b = sched(mix(name), 5), sched(mix(name), 6)
    assert [i.due for i in a] != [i.due for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in b]
    assert sorted(i.prompt_len for i in a) == sorted(i.prompt_len for i in b)
    assert sorted(i.out_len for i in a) == sorted(i.out_len for i in b)
    assert len(a) == len(b) and a[-1].due == pytest.approx(
        max(i.due for i in b))


def test_rate_and_lengths_within_sampling_error():
    m = dict(mix("chat"), arrivals={"kind": "poisson", "rate": 4.0})
    items = sched(m, 1, span=2000.0)
    n = len(items)
    assert abs(n - 8000) < 4 * np.sqrt(8000)
    p = np.asarray([i.prompt_len for i in items], float)
    o = np.asarray([i.out_len for i in items], float)
    assert np.median(p) == pytest.approx(512, rel=0.05)
    assert np.median(o) == pytest.approx(128, rel=0.05)
    # lognormal sigma 1.0: the 84th percentile sits e**1 above the median
    assert np.percentile(p, 84.13) / np.median(p) == pytest.approx(
        np.e, rel=0.08)
    assert p.min() >= 32 and p.max() <= 4096
    assert o.min() >= 8 and o.max() <= 1024


def test_bursts_arrive_on_schedule():
    m = mix("bursty")
    items = sched(m, 3, span=45.0)
    burst = [i for i in items if i.phase == "burst"]
    assert len(burst) == 3 * 16
    starts = sorted({int(i.due // 15) for i in burst})
    assert starts == [0, 1, 2]
    for i in burst:
        assert (i.due - 5.0) % 15.0 <= 1.0
        assert 1024 <= i.prompt_len <= 4096 and 128 <= i.out_len <= 512


def test_backlog_is_a_permuted_fixed_multiset():
    m = mix("longdoc")
    a, b = WL.backlog(m, 1), WL.backlog(m, 2)
    assert len(a) == m["arrivals"]["requests"]
    assert sorted((i.prompt_len, i.out_len) for i in a) == \
        sorted((i.prompt_len, i.out_len) for i in b)
    assert [i.prompt_len for i in a] != [i.prompt_len for i in b]


class FakeEngine:
    def __init__(self):
        self.queue, self.got = [], []
        self.obs = type("O", (), {"step_records": [
            {"config": None, "prefill_tokens": 0, "decode_tokens": 0,
             "attn_ctx_tokens": 0}]})()

    def submit(self, r):
        self.got.append(r)

    def step(self):
        return False


def test_arrivals_are_stamped_at_due_time():
    import repro.engine  # noqa: F401  (imported before the clock starts)
    from bench import harness as H
    eng = FakeEngine()
    drv = H.Driver(eng, 1000, 1)
    items = [WL.Item(d, 4, 2, "window", [1, 2, 3, 4])
             for d in (0.0, 0.05, 0.1)]
    t0 = H.clock()
    drv.serve_open(items, t0, t0 + 0.3)
    assert [r.arrival for r in eng.got] == pytest.approx(
        [t0 + i.due for i in items])
    assert all(0 <= x < 0.05 for x in drv.lateness)


def test_every_mix_file_is_plain_data():
    for f in os.listdir(os.path.join(BENCH, "traffic")):
        assert f.endswith(".json"), f
        with open(os.path.join(BENCH, "traffic", f)) as fh:
            json.load(fh)
