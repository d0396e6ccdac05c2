"""The qwen3-8b 2x2 bursty cell's data and readers: the committed mix gives
three whole bursts in the window, the deployment's slots fit the pool the
configuration's rule leaves on a 16.9e9-byte chip, and the cell's four
per-layer readers read what a run holds (``None``, never 0, where it holds
nothing), on hand-made runs and on a whole run of the tiny 2x2 cell."""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import run as R
from bench import trace_reduce
from bench import workload as WL
from bench.tests import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "qwen3-8b.2x2.bursty"
READERS = ("shift_step_pct", "base_step_ms", "shift_step_ms",
           "collective_pct")
V5E_TRACE = os.path.join(BENCH, "tests", "data", "v5e_decode_steps.xplane.pb")


def cell():
    return H.load_cell(CELL, H.ROOT)


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_window_holds_three_whole_bursts_of_16(seed):
    mix = cell().mix
    items = WL.phase(mix, seed, "window", 51.0)
    burst = [i for i in items if i.phase == "burst"]
    assert len(burst) == 48
    for start in (4.0, 20.0, 36.0):
        mine = [i for i in burst if start <= i.due < start + 1.0]
        assert len(mine) == 16
    assert all(1024 <= i.prompt_len <= 2048 and 128 <= i.out_len <= 256
               for i in burst)
    steady = [i for i in items if i.phase == "window"]
    assert steady and all(256 <= i.prompt_len <= 2048
                          and 64 <= i.out_len <= 256 for i in steady)


def _bytes_per_device(model):
    tree = model.abstract_params()
    shard = model.shardings(model.param_specs())
    return sum(int(np.prod(s.shard_shape(a.shape))) * a.dtype.itemsize
               for a, s in zip(jax.tree.leaves(tree),
                               jax.tree.leaves(shard)))


def test_slots_fit_the_pool_at_16_9e9_bytes():
    """The configuration's rule at published widths: both parameter copies
    as the program shards them, the headroom, and twice the pool."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.parallel import Layout
    c = cell()
    spec, eng = c.spec, c.mix["engine"]
    mesh = make_test_mesh(data=1, sp=spec["mesh"]["sp"],
                          tp=spec["mesh"]["tp"])
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    mc = get_config(spec["arch"])
    base = Model(cfg=mc, lay=lay, mesh=mesh, dtype=jnp.bfloat16)
    shift = Model(cfg=mc, lay=lay.to_shift(), mesh=mesh, dtype=jnp.bfloat16)
    params = _bytes_per_device(base) + _bytes_per_device(shift)
    assert 12.2e9 < params < 12.4e9          # 8.19 GB base + 4.10 GB shift
    bs, slots = int(eng["block_size"]), int(eng["slots"])
    K = spec["num_key_value_heads"] // (spec["mesh"]["sp"]
                                        * spec["mesh"]["tp"])
    block_b = spec["num_hidden_layers"] * 2 * K * bs * spec["head_dim"] * 2
    assert block_b == 589_824
    pool = spec["pool"]
    fits = int((16.9e9 - params - pool["headroom_bytes"])
               // (pool["copies"] * block_b))
    per_slot = -(-WL.max_tokens(c.mix) // bs)
    assert WL.max_tokens(c.mix) == 2048 + 256
    assert slots * per_slot + 1 <= fits


def _step(t0, t1, cfg):
    return (t0, t1, cfg, 4, 0, 4, 0, 100)


def _run(trace=None):
    return {"steps": [_step(0.0, 0.1, "base"), _step(0.1, 0.13, "shift"),
                      _step(0.13, 0.16, "shift"),
                      _step(0.16, 0.19, "shift")],
            "trace": trace}


def read(name, run):
    return R.load_reader(name)(run)


def test_readers_on_a_hand_made_run():
    tr = {"devices": [{"busy_s": 2.0, "collective_s": 0.5},
                      {"busy_s": 1.0, "collective_s": 0.5}]}
    run = _run(tr)
    assert read("shift_step_pct", run) == pytest.approx(75.0)
    assert read("base_step_ms", run) == pytest.approx(100.0)
    assert read("shift_step_ms", run) == pytest.approx(30.0)
    assert read("collective_pct", run) == pytest.approx(37.5)
    tr0 = {"devices": [{"busy_s": 2.0, "collective_s": 0.0}]}
    assert read("collective_pct", _run(tr0)) == 0.0


def test_readers_return_none_where_the_run_holds_nothing():
    assert read("collective_pct", _run(None)) is None
    assert read("collective_pct", _run({"devices": []})) is None
    empty = {"steps": [], "trace": None}
    for name in READERS:
        assert read(name, empty) is None
    only_base = {"steps": [_step(0.0, 0.1, "base")], "trace": None}
    assert read("shift_step_ms", only_base) is None
    assert read("shift_step_pct", only_base) == 0.0


def test_readers_report_on_a_run_of_the_tiny_2x2_cell(monkeypatch):
    """A whole traced run of the tiny cell on a (1, 2, 2) CPU mesh, with
    the cell's readers and the threshold lowered to the tiny cell's size
    (4 batched tokens) so that both programs run. The CPU profiler writes
    no device plane, so the collective reader is given the recorded v5e
    trace's reduction in place of the run's own."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    c = tiny.tiny_cell(monkeypatch, arch="qwen3-8b", sp=2, tp=2)
    bench = json.load(open(os.path.join(H.ROOT, "BENCHMARK.json")))
    layer = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert len(layer) == 4
    c.spec["engine"]["threshold"] = 4
    c = dataclasses.replace(c, layer=layer)
    seen = {}
    orig = R.read_metrics

    def keep(metrics, run, *a):
        seen["run"] = run
        return orig(metrics, run, *a)

    monkeypatch.setattr(R, "read_metrics", keep)
    monkeypatch.setattr(R, "_peaks", lambda kind: {"flops": 1e12,
                                                   "hbm_bytes_per_s": 1e11})
    res = R.run_cell(c, jax.devices()[:4], 2**31 + 3, 1.5, True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert 0.0 < got["shift_step_pct"]["value"] < 100.0
    assert got["base_step_ms"]["value"] > 0
    assert got["shift_step_ms"]["value"] > 0
    run = dict(seen["run"], trace=trace_reduce.reduce(V5E_TRACE))
    for name in READERS:
        assert read(name, run) is not None, name
