"""The harness is driven by data: a new traffic mix and a new per-layer
metric, dropped into a copy of ``bench/`` as new files plus new entries in
``BENCHMARK.json``, are found by name with no existing file edited. And
without a TPU, ``bench/run.py`` fails before it measures."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from bench import harness as H
from bench import run as R

ROOT = H.ROOT


def _digest(root):
    out = {}
    for dp, _, fs in os.walk(os.path.join(root, "bench")):
        if "__pycache__" in dp:
            continue
        for f in fs:
            p = os.path.join(dp, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digest(str(root))
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(json.dumps({
        "engine": {"slots": 2, "chunk": 8, "block_size": 8},
        "arrivals": {"kind": "poisson", "rate": 1.0},
        "prompt": {"dist": "uniform", "lo": 4, "hi": 8},
        "output": {"dist": "uniform", "lo": 2, "hi": 4}}))
    (root / "bench" / "metrics" / "steps_in_window.py").write_text(
        '"""steps_in_window: launched steps."""\n\n\n'
        'def read(run):\n    return float(len(run["steps"]))\n')
    (root / "bench" / "cells" / "qwen2-1.5b.tiny_mix.json").write_text(
        json.dumps({"mean_gap_limit": 1.0, "check_tokens": 8,
                    "check_requests": 1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen2-1.5b.tiny_mix",
                               "config": "qwen2-1.5b", "traffic": "tiny_mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler (engine/engine.py)",
                               "moves": "tpot_p50_ms",
                               "workloads": ["qwen2-1.5b.tiny_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = H.load_cell("qwen2-1.5b.tiny_mix", str(root))
    assert cell.mix["engine"]["slots"] == 2
    assert "steps_in_window" in [m["name"] for m in cell.layer]
    got = R.read_metrics(cell.layer, {"steps": [1, 2, 3], "trace": None},
                         bench_dir=str(root / "bench"))
    assert got["steps_in_window"] == {"value": 3.0, "unit": "steps"}
    after = _digest(str(root))
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(R.load_reader(m["name"])), m["name"]
    for w in bench["workloads"]:
        H.load_cell(w["name"])


def test_run_without_a_tpu_fails_before_measuring(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, str(tmp_path)):
        if root != ROOT:             # only BENCHMARK.json and bench/
            shutil.copytree(os.path.join(ROOT, "bench"),
                            os.path.join(root, "bench"),
                            ignore=shutil.ignore_patterns("__pycache__"))
            shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.longdoc",
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=root, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert '"correct"' not in p.stdout
