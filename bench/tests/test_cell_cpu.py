"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: sound runs come out correct, and runs with the timed path broken
underneath come out not correct (a token altered where it is produced; a
step that leaves the KV pool unchanged; on a 2x2 mesh, the exchange
between chips left out), as does the check's control in the served
tokens' place."""
import jax
import pytest

from bench import run as R
from bench.tests import tiny

PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11}


def run(monkeypatch, seconds=1.5, trace=False, control=False, **kw):
    cell = tiny.tiny_cell(monkeypatch, **kw)
    monkeypatch.setattr(R, "_peaks", lambda kind: PEAKS)
    return R.run_cell(cell, jax.devices()[:cell.chips], 2**31 + 9, seconds,
                      trace, control)


def test_sound_open_loop_run_is_correct(monkeypatch):
    res = run(monkeypatch)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert {"kind", "count", "platform", "memory_peak_bytes"} <= \
        set(res["device"])
    assert {"tpot_p50_ms", "setup_s"} <= set(res["metrics"])


def test_sound_backlog_traced_run_is_correct(monkeypatch):
    res = run(monkeypatch, kind="backlog", traffic="longdoc", trace=True)
    assert res["correct"], res["checks"]
    assert "rows_per_step" in res["metrics"]
    assert "breakdown" in res and "busy_s" in res["device"]


def test_control_in_the_served_tokens_place_is_not_correct(monkeypatch):
    res = run(monkeypatch, kind="backlog", control=True)
    c = res["checks"]
    assert not res["correct"]
    assert c["mean_logit_gap"]["value"] > c["mean_logit_gap"]["limit"]
    assert c["program_mean_logit_gap"]["value"] <= \
        c["program_mean_logit_gap"]["limit"]


def _alter_tokens(monkeypatch):
    from repro.models.model import Model
    orig = Model.forward_fn

    def broken(self, *a, **k):
        f = orig(self, *a, **k)

        def g(*args):
            nxt, pool = f(*args)
            return (nxt + 1) % self.cfg.vocab_size, pool
        return g
    monkeypatch.setattr(Model, "forward_fn", broken)


def test_altered_token_is_not_correct(monkeypatch):
    _alter_tokens(monkeypatch)
    res = run(monkeypatch)
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > \
        res["checks"]["mean_logit_gap"]["limit"]


def test_pool_left_unchanged_is_not_correct(monkeypatch):
    """A step that returns its state (the paged KV pool) unchanged."""
    from repro.models.model import Model
    orig = Model.forward_fn

    def broken(self, *a, **k):
        f = orig(self, *a, **k)

        def g(params, pool, *args):
            nxt, _ = f(params, pool, *args)
            return nxt, pool
        return g
    monkeypatch.setattr(Model, "forward_fn", broken)
    res = run(monkeypatch, kind="backlog")
    assert not res["correct"]


@pytest.fixture
def four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices (XLA_FLAGS "
                    "--xla_force_host_platform_device_count=4)")


def test_sound_2x2_run_is_correct(monkeypatch, four_devices):
    res = run(monkeypatch, arch="qwen3-8b", sp=2, tp=2)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4


def test_exchange_left_out_is_not_correct(monkeypatch, four_devices):
    import repro.models.attention as A
    import repro.models.layers as L
    for mod in (A, L):
        monkeypatch.setattr(mod, "psum_if", lambda x, axes: x)
    res = run(monkeypatch, arch="qwen3-8b", sp=2, tp=2)
    assert not res["correct"]
