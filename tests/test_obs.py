"""Observability: schema-strict metrics registry + exporters, the
one-source-of-truth step records (the step_times/step_log desync bugfix),
engine/sim schema conformance, deterministic reports, snapshot/restore of
observability state, and the served step's spans and counters (host
phases, launched shape, KV coverage, compiles) in the step records and on
the profiler's clock."""
import importlib
import itertools
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.policy import ThresholdPolicy
from repro.engine import (ObsConfig, PrefixConfig, ShiftEngine,
                          EngineConfig, Request)
from repro.models import build_model
from repro.obs import (schema, MetricsRegistry, NullObs, Observability,
                       build_report)
from repro.obs.events import EventLog
from repro.obs.report import percentile


# --------------------------------------------------------------- registry
def test_counter_is_monotone():
    reg = MetricsRegistry()
    c = reg.counter("requests_arrived_total")
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_registry_is_schema_strict():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("made_up_total")
    with pytest.raises(ValueError):
        reg.counter("requests_arrived_total", config="base")  # no labels
    with pytest.raises(ValueError):
        reg.counter("steps_total")                # missing config label
    with pytest.raises(ValueError):
        reg.counter("steps_total", config="bogus")
    with pytest.raises(ValueError):
        reg.gauge("made_up_depth")
    with pytest.raises(ValueError):
        reg.histogram("made_up_seconds")


def test_histogram_buckets_and_prometheus():
    reg = MetricsRegistry()
    h = reg.histogram("ttft_seconds")
    for v in (0.0001, 0.003, 0.003, 0.7, 500.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(500.7062)
    assert h.buckets[-1] == 1                     # 500s > last bound
    reg.counter("steps_total", config="base").inc(3)
    reg.gauge("queue_depth").set(2)
    text = reg.to_prometheus()
    assert "# TYPE repro_ttft_seconds histogram" in text
    assert "# TYPE repro_steps_total counter" in text
    assert 'repro_steps_total{config="base"} 3' in text
    assert "repro_queue_depth 2" in text
    assert 'repro_ttft_seconds_bucket{le="+Inf"} 5' in text
    assert "repro_ttft_seconds_count 5" in text
    # buckets are cumulative: each le line is >= the previous
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
              if ln.startswith("repro_ttft_seconds_bucket")]
    assert counts == sorted(counts)


def test_gauge_set_max():
    reg = MetricsRegistry()
    g = reg.gauge("shared_blocks_peak")
    g.set_max(4)
    g.set_max(2)
    assert g.value == 4.0


def test_registry_state_roundtrip():
    reg = MetricsRegistry()
    reg.counter("steps_total", config="shift").inc(7)
    reg.histogram("step_seconds").observe(0.02)
    reg.gauge("free_blocks").set(11)
    reg2 = MetricsRegistry()
    reg2.load_state(reg.state_dict())
    assert reg2.snapshot() == reg.snapshot()
    assert reg2.to_prometheus() == reg.to_prometheus()


def test_event_log_schema_and_cap():
    log = EventLog(cap=4)
    with pytest.raises(ValueError):
        log.emit("made_up_kind", step=0, ts=0.0)
    for i in range(6):
        log.emit("queued", step=i, ts=float(i), rid=i)
    assert len(log.events) == 4 and log.dropped == 2
    assert log.events[0]["step"] == 2             # oldest dropped
    assert [e["seq"] for e in log.events] == [2, 3, 4, 5]


def test_percentile_linear_interpolation():
    import numpy as np
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    for p in (0, 25, 50, 90, 99, 100):
        assert percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    assert percentile([], 50) != percentile([], 50)          # NaN
    assert percentile([7.0], 99) == 7.0


# ------------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def mp():
    cfg = reduced_cfg("qwen3-8b")
    m = build_model(cfg, dtype=jnp.float32)
    return m, m.init_params(jax.random.key(0))


def _fake_clock():
    c = itertools.count()
    return lambda: next(c) * 1e-3


def _run_engine(mp, n_req=3, n_new=5, prefix_cache=False, obs=True, **kw):
    m, params = mp
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8,
                        prefix=PrefixConfig(enabled=prefix_cache),
                        obs=ObsConfig(enabled=obs), **kw)
    eng = ShiftEngine(m, m, params, params, ecfg,
                      policy=ThresholdPolicy(4), now=_fake_clock())
    for i in range(n_req):
        eng.add_request(Request(i, list(range(1, 12 + 3 * i)),
                                max_new_tokens=n_new))
    eng.run_until_idle()
    return eng


def test_step_records_carry_index_and_duration(mp):
    """THE bugfix: step index + duration live INSIDE each step record, so
    the rolling window can never desynchronize step_times from step_log."""
    eng = _run_engine(mp, n_req=4, n_new=8)
    total_before = eng.total_step_time
    eng.trace_window = 4                          # trim retroactively
    assert len(eng.step_log) == 4
    steps = [r["step"] for r in eng.step_log]
    assert steps == sorted(steps) and steps[-1] == eng.step_count - 1
    # the views index-align because they come from the same records
    assert eng.step_times == [r["dur_s"] for r in eng.step_log]
    assert all("dur_s" in r and "step" in r for r in eng.step_log)
    # totals are histogram-backed, not window-backed: trimming loses nothing
    assert eng.total_step_time == total_before
    assert eng.total_step_time >= sum(eng.step_times)


def test_engine_dump_is_deterministic(mp):
    """Two same-seed runs with the injected fake clock produce bitwise
    identical dumps and reports (the acceptance criterion)."""
    d1 = _run_engine(mp, prefix_cache=True).obs.dump()
    d2 = _run_engine(mp, prefix_cache=True).obs.dump()
    assert json.dumps(d1, sort_keys=True) == json.dumps(d2, sort_keys=True)
    r1, r2 = build_report(d1), build_report(d2)
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)
    assert r1["requests"]["finished"] == 3


def test_engine_lifecycle_events(mp):
    eng = _run_engine(mp, n_req=2, prefix_cache=True)
    ev = eng.obs.events
    for rid in (0, 1):
        kinds = [e["kind"] for e in ev.for_request(rid)]
        for k in ("queued", "routed", "admitted", "prefill_chunk",
                  "first_token", "finish"):
            assert k in kinds, (rid, k, kinds)
        # span ordering follows the lifecycle
        assert kinds.index("queued") < kinds.index("admitted") \
            < kinds.index("first_token") < kinds.index("finish")
    fin = ev.for_request(0)[-1]
    assert fin["kind"] == "finish" and fin["n_out"] == 5
    assert fin["ttft_s"] is not None and fin["e2e_s"] > fin["ttft_s"]


def test_nullobs_engine_matches_instrumented(mp):
    """obs=False must not change scheduling — only recording."""
    e_on = _run_engine(mp, n_req=2)
    e_off = _run_engine(mp, n_req=2, obs=False)
    on = {r.rid: tuple(r.generated) for r in e_on.queue}
    off = {r.rid: tuple(r.generated) for r in e_off.queue}
    assert on == off and e_on.step_count == e_off.step_count
    assert e_off.step_log == [] and e_off.obs.enabled is False
    assert e_off.obs.state_dict() is None


@pytest.mark.parametrize("mixed", [None, False])
def test_snapshot_restore_carries_obs_state(mp, mixed):
    """Counters stay monotone and in-flight request spans resume across a
    restore, on both the mixed and the serialized scheduling paths."""
    m, params = mp
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8,
                        prefix=PrefixConfig(enabled=True), mixed=mixed)
    eng = ShiftEngine(m, m, params, params, ecfg,
                      policy=ThresholdPolicy(4), now=_fake_clock())
    for i in range(3):
        eng.add_request(Request(i, list(range(1, 14 + 2 * i)),
                                max_new_tokens=6))
    for _ in range(4):
        eng.step()
    arrived = eng.obs.registry.counter_total("requests_arrived_total")
    steps_before = eng.obs.registry.counter_total("steps_total")
    snap = eng.snapshot()

    eng2 = ShiftEngine(m, m, params, params, ecfg,
                       policy=ThresholdPolicy(4), now=_fake_clock())
    eng2.restore(snap)
    assert eng2.step_count == eng.step_count
    eng2.run_until_idle()
    reg = eng2.obs.registry
    # monotone: arrivals came over in the snapshot, not re-counted
    assert reg.counter_total("requests_arrived_total") == arrived == 3
    assert reg.counter_total("steps_total") >= steps_before
    assert reg.counter_total("requests_finished_total") == 3
    # in-flight spans resume: pre-snapshot queued + post-restore finish
    # live in ONE event log, joined by rid
    ev = eng2.obs.events
    for rid in range(3):
        kinds = [e["kind"] for e in ev.for_request(rid)]
        assert "queued" in kinds and "finish" in kinds
    kinds_all = [e["kind"] for e in ev.events]
    assert "snapshot" in kinds_all and "restore" in kinds_all
    # step records keep one monotone index stream across the restore
    steps = [r["step"] for r in eng2.step_log]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)


def test_serialized_snapshot_after_restore_resumes_stream(mp):
    """Serialized path end-to-end equivalence: restored engine finishes
    the same token streams the uninterrupted engine produces."""
    m, params = mp
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8, mixed=False)

    def fresh():
        eng = ShiftEngine(m, m, params, params, ecfg,
                          policy=ThresholdPolicy(4), now=_fake_clock())
        for i in range(2):
            eng.add_request(Request(i, list(range(1, 16)), max_new_tokens=5))
        return eng

    ref = fresh()
    ref.run_until_idle()
    want = {r.rid: tuple(r.generated) for r in ref.queue}
    eng = fresh()
    for _ in range(3):
        eng.step()
    eng2 = ShiftEngine(m, m, params, params, ecfg,
                       policy=ThresholdPolicy(4), now=_fake_clock())
    eng2.restore(eng.snapshot())
    eng2.run_until_idle()
    got = {r.rid: tuple(r.generated) for r in eng2.queue}
    assert got == want
    assert eng2.obs.registry.counter_total("requests_finished_total") == 2


# ------------------------------------------------------ schema conformance
def _run_sim():
    from repro.configs import get_config
    from repro.roofline.terms import H200
    from repro.sim.costmodel import CostModel
    from repro.sim.simulator import ServeSim, SimRequest
    sim = ServeSim(CostModel(get_config("qwen3-8b"), hw=H200), "shift",
                   n_chips=8, prefix_cache=True)
    reqs = [SimRequest(i, 0.05 * i, 256 + 64 * (i % 3), 16,
                       prefix_id=0, prefix_len=128) for i in range(8)]
    sim.run(reqs)
    return sim


def _assert_within_schema(obs):
    names = obs.registry.emitted_names()
    assert names["counters"] <= set(schema.COUNTERS), \
        names["counters"] - set(schema.COUNTERS)
    assert names["gauges"] <= set(schema.GAUGES)
    assert names["histograms"] <= set(schema.HISTOGRAMS)
    assert {e["kind"] for e in obs.events.events} <= set(schema.EVENTS)
    for r in obs.step_records:
        schema.check_step_record(r)
    return names


def test_engine_and_sim_share_one_schema(mp):
    """The acceptance criterion: both emitters stay within the declared
    vocabulary and share the core counter subset, so their dumps feed the
    same report/trace consumers."""
    eng = _run_engine(mp, prefix_cache=True)
    sim = _run_sim()
    n_eng = _assert_within_schema(eng.obs)
    n_sim = _assert_within_schema(sim.obs)
    core = set(schema.CORE_COUNTERS)
    assert core <= n_eng["counters"], core - n_eng["counters"]
    assert core <= n_sim["counters"], core - n_sim["counters"]
    # the same report pipeline consumes both dumps
    r_eng = build_report(eng.obs.dump())
    r_sim = build_report(sim.obs.dump())
    assert set(r_eng) == set(r_sim)
    assert set(r_eng["latency"]) == set(r_sim["latency"])
    assert r_sim["requests"]["finished"] == 8


def test_sim_legacy_counters_are_registry_views():
    sim = _run_sim()
    reg = sim.obs.registry
    assert sim.iterations == reg.counter_total("steps_total") > 0
    assert sim.prefill_tokens_saved \
        == reg.counter_total("prefix_tokens_saved_total") > 0
    assert sim.starved_steps \
        == reg.counter_total("decode_starved_steps_total")
    assert sim.shared_blocks_peak \
        == reg.gauge_value("shared_blocks_peak") > 0
    # sim steps label with the engine's config vocabulary (base/shift)
    cfgs = {r["config"] for r in sim.obs.step_records}
    assert cfgs <= {"base", "shift"}


# --------------------------------------------- step spans and counters
PHASES = ("admit", "plan", "inputs", "launch", "wait", "apply")


def _launched(eng):
    return [r for r in eng.step_log if r["config"] is not None]


@pytest.mark.parametrize("mixed", [None, False])
def test_launched_steps_carry_host_phases(mp, mixed):
    """Every launched step's record splits its host time by phase; the
    phases nest inside the step, so they never sum past its duration."""
    eng = _run_engine(mp, n_req=3, n_new=4, mixed=mixed)
    recs = _launched(eng)
    assert recs
    for r in recs:
        schema.check_step_record(r)
        ph = r["host_s"]
        assert set(ph) <= set(PHASES)
        assert {"admit", "plan", "inputs", "launch", "apply"} <= set(ph), ph
        assert all(v >= 0 for v in ph.values())
        assert sum(ph.values()) <= r["dur_s"] + 1e-12
    # a step that samples tokens waits for them; the serialized prefill
    # reads nothing back
    assert all("wait" in r["host_s"] for r in recs if r["decode_tokens"])


def _launch_ctx(table, args):
    """Each launched live row's context after the step's write, from the
    arrays a step program was called with."""
    if table == "forward":             # tokens, q_lens, offsets, tables
        return (args[2] + args[1])[args[1] > 0]
    live = (args[-1] != 0).any(axis=1)  # serialized: every slot launches
    if table == "prefill":             # tokens, offsets, tables
        return (args[1] + (args[0] != 0).sum(axis=1))[live]
    return (args[1] + 1)[live]         # decode: tokens, lens, tables


@pytest.mark.parametrize("mixed", [None, False])
def test_bucket_and_kv_blocks_match_the_launched_arrays(mp, mixed):
    m, params = mp
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8, mixed=mixed)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    seen = []
    for table in ("forward", "prefill", "decode"):
        fns = getattr(eng.deploy, table) or {}
        for mode, fn in fns.items():
            def spy(p, cache, *args, _fn=fn, _table=table):
                seen.append((_table, [np.asarray(a) for a in args]))
                return _fn(p, cache, *args)
            fns[mode] = spy
    for i in range(3):                 # prompt tokens are never 0
        eng.add_request(Request(i, list(range(1, 12 + 9 * i)),
                                max_new_tokens=4))
    eng.run_until_idle()
    recs = _launched(eng)
    assert len(recs) == len(seen) > 0
    assert {t for t, _ in seen} == ({"forward"} if mixed is None
                                    else {"prefill", "decode"})
    bs = ecfg.block_size
    for rec, (table, args) in zip(recs, seen):
        toks, bt = args[0], args[-1]
        assert rec["bucket"] == [toks.shape[0],
                                 toks.shape[1] if toks.ndim > 1 else 1,
                                 bt.shape[1]]
        ctx = _launch_ctx(table, args)
        assert len(ctx) == rec["n_rows"]
        assert rec["kv_blocks"] == int(sum(-(-int(c) // bs) for c in ctx))
        assert rec["kv_blocks"] <= rec["bucket"][0] * rec["bucket"][2]


@pytest.mark.parametrize("mixed", [None, False])
def test_launch_record_carries_kv_step_blocks(mp, mixed, monkeypatch):
    """Each paged launch records ``kv_step_blocks``, the KV blocks per grid
    step of its attention kernel: the engine asks the kernel's own helper
    at the shapes the kernel sees, so the record matches what the kernel
    traced with at that table width."""
    # the package re-exports a function under the module's name
    pra = importlib.import_module("repro.kernels.paged_ragged_attention")
    helper, traced = pra.kv_step_blocks, set()

    def spy(**shapes):
        p = helper(**shapes)
        traced.add((shapes["nmax"], p))
        return p

    monkeypatch.setattr(pra, "kv_step_blocks", spy)
    eng = _run_engine(mp, n_req=3, n_new=4, mixed=mixed)
    recs = _launched(eng)
    assert recs and traced
    for r in recs:
        schema.check_step_record(r)
        p = r["kv_step_blocks"]
        assert 1 <= p <= r["bucket"][2]
        assert (r["bucket"][2], p) in traced


def test_compiles_counted_on_new_buckets_only(mp):
    """A step that launches a bucket first compiles; the same bucket again
    builds nothing."""
    eng = _run_engine(mp, n_req=2, n_new=6)
    recs = _launched(eng)
    first = {}
    for r in recs:
        key = (r["config"], tuple(r["bucket"]))
        if key not in first:
            first[key] = r
            assert r["compiles"] > 0, r
        else:
            assert r["compiles"] == 0, r
    assert len(first) < len(recs)         # some bucket ran twice
    assert all(r["compiles"] == 0 for r in eng.step_log
               if r["config"] is None)


def test_nullobs_span_records_nothing(mp):
    null = NullObs()
    assert null.span("engine.plan") is null.span("engine.step", step=3)
    with null.span("engine.step", step=0):
        with null.span("engine.plan"):
            pass
    assert null.phases == {}
    eng = _run_engine(mp, n_req=2, obs=False)
    assert eng.obs.phases == {} and eng.step_log == []


def test_span_phases_add_up_and_step_span_resets(mp):
    t = iter(range(100))
    obs = Observability("engine", now=lambda: float(next(t)))
    with obs.span("engine.step", step=7):
        with obs.span("engine.plan"):
            pass
        with obs.span("engine.plan"):
            pass
        with obs.span("engine.launch"):
            pass
    assert obs.phases == {"plan": 2.0, "launch": 1.0}
    with obs.span("engine.step", step=8):
        assert obs.phases == {}


def test_step_programs_carry_stable_names(mp):
    mixed = _run_engine(mp, n_req=1, n_new=2)
    assert {c: f.__name__ for c, f in mixed.deploy.forward.items()} == \
        {"base": "step_base", "shift": "step_shift"}
    serial = _run_engine(mp, n_req=1, n_new=2, mixed=False)
    assert serial.deploy.prefill["shift"].__name__ == "prefill_shift"
    assert serial.deploy.decode["base"].__name__ == "decode_base"


def _xplane_events(trace_dir):
    import glob
    import os
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    with warnings.catch_warnings():      # the stats type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        return [(e.name, dict(e.stats)) for plane in pd.planes
                for line in plane.lines for e in line.events]


def test_profiler_trace_holds_engine_spans(mp, tmp_path):
    """The phases land on the profiler's host plane; ``engine.step``
    carries the step index as its ``step_num`` stat (not in its name)."""
    m, params = mp
    ecfg = EngineConfig(max_slots=4, s_max=64, prefill_chunk=8)
    eng = ShiftEngine(m, m, params, params, ecfg, policy=ThresholdPolicy(4))
    eng.add_request(Request(0, list(range(1, 20)), max_new_tokens=3))
    eng.step()                                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    evs = _xplane_events(str(tmp_path))
    names = {n for n, _ in evs if n.startswith("engine.")}
    assert {"engine." + p for p in PHASES} | {"engine.step"} <= names
    steps = [st["step_num"] for n, st in evs if n == "engine.step"]
    assert sorted(steps) == [1, 2, 3]
    assert [r["step"] for r in eng.step_log[1:]] == [1, 2, 3]
