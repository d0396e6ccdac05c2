"""The serving entry point: the CLI reaches the published widths and a
(1, sp, tp) mesh, every parameter and pool leaf is created in its own
sharding, and the compile cache lands where it is told to."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.launch.compile_cache as CC
import repro.launch.serve as serve


class _Reached(Exception):
    pass


@pytest.mark.parametrize("argv,reduced,layers,sp,tp", [
    ([], True, 0, 1, 1),
    (["--no-reduced", "--num-layers", "18", "--sp", "2", "--tp", "2"],
     False, 18, 2, 2),
])
def test_cli_reaches_build_stack(monkeypatch, capsys, argv, reduced, layers,
                                 sp, tp):
    """``--no-reduced`` (impossible before: the flag was store_true with
    default True) and the depth/mesh options arrive at ``_build_stack``."""
    seen = {}

    def fake_stack(arch, **kw):
        seen.update(kw)
        raise _Reached

    monkeypatch.setattr(serve, "_build_stack", fake_stack)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    with pytest.raises(_Reached):
        serve.main(argv)
    assert (seen["reduced"], seen["num_layers"], seen["sp"], seen["tp"]) \
        == (reduced, layers, sp, tp)
    out = capsys.readouterr().out
    assert ("depth cut" in out) == bool(layers)


def test_build_stack_dtype_and_depth():
    base, shift, *_ = serve._build_stack("qwen3-8b", num_layers=1)
    assert base.dtype == jnp.float32 and base.cfg.num_layers == 1
    assert shift is base                       # one device: one model
    with pytest.raises(ValueError):
        serve._build_stack("qwen3-8b", num_layers=99)


def _assert_placed(tree, model, specs):
    isp = lambda x: isinstance(x, P)  # noqa: E731
    leaves = jax.tree.leaves(tree)
    spec_leaves = jax.tree.leaves(specs, is_leaf=isp)
    assert len(leaves) == len(spec_leaves)
    for a, spec in zip(leaves, spec_leaves):
        want = NamedSharding(model.mesh, spec)
        assert isinstance(a.sharding, NamedSharding)
        assert a.sharding.mesh == model.mesh
        assert a.sharding.is_equivalent_to(want, a.ndim), (a.shape, spec)


def test_sp_tp_mesh_places_every_leaf():
    """``sp=2, tp=2`` builds a 1x2x2 mesh with base = SP2xTP2 and shift =
    TP4; params and the KV pool are born in their shardings."""
    eng = serve.build_engine("qwen3-8b", sp=2, tp=2, slots=4, s_max=32,
                             chunk=8)
    base, shift = eng.base, eng.shift
    assert base.mesh.devices.shape == (1, 2, 2)
    assert (base.lay.sp, base.lay.tp) == (2, 2)
    assert (shift.lay.sp, shift.lay.tp) == (1, 4)
    _assert_placed(eng.p_base, base, base.param_specs())
    _assert_placed(eng.p_shift, shift, shift.param_specs())
    _assert_placed(eng.cache, base, base.paged_cache_specs())


def test_compile_cache_honours_env(monkeypatch):
    calls = []
    monkeypatch.setattr(CC.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(CC.CACHE_ENV, "/somewhere/else")
    assert CC.enable_compile_cache() == "/somewhere/else"
    assert calls == []                      # JAX reads the variable itself
    monkeypatch.delenv(CC.CACHE_ENV)
    path = CC.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()   # never committed


def test_profile_dir_writes_a_trace_of_the_engine_spans(monkeypatch, capsys,
                                                        tmp_path):
    """``--profile-dir`` records the serve loop with the JAX profiler: the
    Perfetto trace holds the engine's step spans with their index."""
    import glob
    import gzip
    import json
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    serve.main(["--requests", "2", "--max-new", "3",
                "--profile-dir", str(tmp_path)])
    assert f"profiler trace written under {tmp_path}" in \
        capsys.readouterr().out
    path, = glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                      recursive=True)
    with gzip.open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"] if isinstance(doc, dict) else doc
    steps = [e for e in evs if e.get("name") == "engine.step"]
    assert steps and all("step_num" in e["args"] for e in steps)
    assert {"engine.launch", "engine.wait"} <= {e.get("name") for e in evs}
