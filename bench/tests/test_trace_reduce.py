"""The trace reduction on a recorded TPU v5e trace: three 64-row decode
steps of qwen2-1.5b (28 layers, 11000-block pool) inside ``bench_step``
spans, with 2 ms ``bench_gap`` sleeps between them."""
import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_decode_steps.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return T.reduce(DATA)


def test_busy_and_window(red):
    assert red["window_s"] == pytest.approx(1.2598, abs=1e-3)
    assert 0 < red["busy_s"] <= red["window_s"]
    # the three module launches took 416.7 ms each
    assert red["busy_s"] == pytest.approx(3 * 0.4167, rel=0.01)


def test_kernel_and_collectives(red):
    assert red["kernel_s"] == pytest.approx(1.0285, rel=1e-3)
    assert red["collective_s"] == 0.0            # one chip: no collectives
    assert red["device_ops"][0][0] == "pallas:tpu_custom_call"
    ops = sum(t for _, t in red["device_ops"])
    assert ops <= red["busy_s"] * 1.0001         # self times never overlap


def test_gaps_are_labelled_by_host_spans(red):
    labels = dict(red["idle_gaps"])
    assert set(labels) <= {"bench_step", "bench_gap", "outside_bench_spans"}
    idle = red["window_s"] - red["busy_s"]
    assert sum(labels.values()) == pytest.approx(idle, rel=1e-6)
    assert labels["bench_step"] > 0.005          # host side of each step


def test_short_names():
    assert T.short_name("%fusion.147 = bf16[64] fusion(x)") == "fusion"
    assert T.short_name("%all-reduce.3 = f32[8] all-reduce(x)") \
        == "all-reduce"
    assert T.short_name('%closed_call.13 = bf16[1] custom-call(x), '
                        'custom_call_target="tpu_custom_call"') \
        == "pallas:tpu_custom_call"


def test_union_and_self_times():
    assert T._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    st = dict(T._self_times([(0, 10, "while"), (1, 4, "a"), (5, 9, "b")]))
    assert st == {"while": 3, "a": 3, "b": 4}
