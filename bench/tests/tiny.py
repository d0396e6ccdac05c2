"""A tiny cell for CPU tests: the program's reduced preset, float32, a
small open-loop or backlog mix, the harness unchanged."""
from __future__ import annotations

import dataclasses
import json
import os

from bench import harness as H

ROOT = H.ROOT


def tiny_spec(arch="qwen2-1.5b", sp=1, tp=1):
    from repro.configs import get_config
    mc = get_config(arch).reduced()
    if sp * tp > 1:       # heads that split evenly over the model group
        mc = dataclasses.replace(mc, num_heads=8, num_kv_heads=4)
    spec = {"arch": arch, "hidden_size": mc.d_model,
            "intermediate_size": mc.d_ff,
            "num_attention_heads": mc.num_heads,
            "num_key_value_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim, "num_hidden_layers": mc.num_layers,
            "vocab_size": mc.vocab_size, "rope_theta": mc.rope_theta,
            "rms_norm_eps": mc.norm_eps,
            "tie_word_embeddings": mc.tie_embeddings,
            "qkv_bias": mc.qkv_bias, "qk_norm": mc.qk_norm,
            "dtype": "float32", "mesh": {"chips": sp * tp, "sp": sp, "tp": tp},
            "pool": {"headroom_bytes": 0, "copies": 2},
            "engine": {"threshold": 32}}
    return spec, mc


def tiny_mix(kind="poisson"):
    mix = {"engine": {"slots": 4, "chunk": 16, "block_size": 8},
           "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "lo": 4, "hi": 40},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "lo": 2, "hi": 12},
           "warmup_s": 0.5, "trace": {"offset_s": 0.2, "seconds": 0.5}}
    if kind == "poisson":
        mix["arrivals"] = {"kind": "poisson", "rate": 8.0}
    else:
        mix["arrivals"] = {"kind": "backlog", "requests": 12, "queue": 2}
    return mix


def tiny_cell(monkeypatch, arch="qwen2-1.5b", kind="poisson", sp=1, tp=1,
              limit=1e-3, traffic="longdoc"):
    spec, mc = tiny_spec(arch, sp, tp)
    monkeypatch.setattr("repro.configs.get_config", lambda a: mc)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    like = {"longdoc": "qwen2-1.5b.longdoc"}[traffic]
    return H.Cell(
        name="tiny", entry={"name": "tiny", "chips": sp * tp},
        spec=spec, mix=tiny_mix(kind),
        local={"mean_gap_limit": limit, "check_tokens": 32,
               "check_requests": 4},
        e2e=[m for m in bench["end_to_end"] if H._reports(m, like)],
        layer=[m for m in bench["per_layer"] if H._reports(m, like)],
        chips=sp * tp)
