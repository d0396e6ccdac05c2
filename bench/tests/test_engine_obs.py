"""The reductions and readers of the served step's own spans and counters:
on the recorded TPU v5e trace (three 64-row decode steps of a program that
had neither, whose step module was ``jit_body``), on a CPU profiler trace
of the engine, and on hand-built run records."""
import os

import jax
import pytest

from bench import engine_obs as E
from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_decode_steps.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return E.reduce(DATA)


def test_modules_of_the_recorded_trace(red):
    mods = red["modules"]
    assert set(mods) == {"jit_body"}
    assert mods["jit_body"]["launches"] == 3
    assert mods["jit_body"]["seconds"] == pytest.approx(3 * 0.4167, rel=1e-3)
    assert red["engine_spans"] == {}


def test_idle_gaps_cover_the_idle_time(red):
    base = T.reduce(DATA)
    labels = dict(red["idle_gaps"])
    assert set(labels) <= {"bench_step", "bench_gap", "outside_spans"}
    idle = base["window_s"] - base["busy_s"]
    assert sum(labels.values()) == pytest.approx(idle, rel=1e-6)
    # split at span boundaries: the two 2 ms sleeps between the steps are
    # idle in ``bench_gap`` (labelled by its midpoint, a whole gap went to
    # the ``bench_step`` around its middle); the host side of each step
    # stays the most
    assert labels["bench_gap"] > 0.003
    assert max(labels, key=labels.get) == "bench_step"
    assert labels["bench_step"] < dict(base["idle_gaps"])["bench_step"]


def _tiny_engine():
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import EngineConfig, Request, ShiftEngine
    from repro.models import build_model
    m = build_model(get_config("qwen2-1.5b").reduced(), dtype=jnp.float32)
    p = m.init_params(jax.random.key(0))
    eng = ShiftEngine(m, m, p, p, EngineConfig(max_slots=2, s_max=64,
                                               prefill_chunk=8),
                      policy=ThresholdPolicy(4))
    eng.add_request(Request(0, list(range(1, 20)), max_new_tokens=6))
    return eng


def test_engine_spans_of_a_cpu_trace(tmp_path):
    eng = _tiny_engine()
    eng.step()                                   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            eng.step()
    jax.profiler.stop_trace()
    spans = E.reduce(str(tmp_path))["engine_spans"]
    assert spans["engine.step"]["count"] == 3
    for phase in ("admit", "plan", "inputs", "launch", "wait", "apply"):
        s = spans["engine." + phase]
        assert s["count"] >= 3 and 0 < s["seconds"]
    assert sum(s["seconds"] for k, s in spans.items()
               if k != "engine.step") <= spans["engine.step"]["seconds"]


def _rec(t, host, bucket, kv, compiles=0, config="base"):
    return {"t_start": t, "config": config, "host_s": host,
            "bucket": bucket, "kv_blocks": kv, "compiles": compiles}


def _run():
    recs = [_rec(0.0, {"plan": 0.002, "wait": 0.3}, [32, 1, 512], 8192, 2),
            _rec(1.0, {"plan": 0.001, "launch": 0.002, "wait": 0.3},
                 [32, 1, 512], 8224),
            _rec(2.0, {"plan": 0.004, "wait": 0.3}, [32, 1, 512], 8256)]
    return {"step_records": recs,
            "steps": [(r["t_start"], r["t_start"] + 0.5) for r in recs],
            "trace_steps": [(1.0, 1.5), (2.0, 2.5)],
            "trace": {"modules": {
                "jit_step_base": {"launches": 2, "seconds": 0.7},
                "jit_step_shift": {"launches": 2, "seconds": 0.5},
                "jit_init": {"launches": 1, "seconds": 9.0}}}}


def test_readers_on_a_hand_built_run():
    run = _run()
    assert E.device_step_ms(run) == pytest.approx(300.0)
    # the traced slice holds the last two steps
    assert E.host_step_ms(run) == pytest.approx(3.5)
    assert E.kv_grid_used_pct(run) == pytest.approx(
        100 * (8192 + 8224 + 8256) / (3 * 32 * 512))
    assert E.compiles_in_window(run) == 2


def test_readers_of_a_program_without_spans_or_counters():
    """The parent program: its records lack the fields, its step module is
    ``jit_body``. Every reader reads nothing and raises nothing."""
    run = _run()
    run["step_records"] = [{k: v for k, v in r.items()
                            if k in ("t_start", "config")}
                           for r in run["step_records"]]
    run["trace"] = {"modules": {"jit_body": {"launches": 3,
                                             "seconds": 1.25}}}
    for read in (E.device_step_ms, E.host_step_ms, E.kv_grid_used_pct,
                 E.compiles_in_window):
        assert read(run) is None
    assert all(read({"trace": None}) is None
               for read in (E.device_step_ms, E.host_step_ms,
                            E.kv_grid_used_pct, E.compiles_in_window))
