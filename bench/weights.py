"""Seeded random weights of a dense GQA decoder, drawn by the benchmark.

The benchmark, not the program, owns the weights: the served path gets them
in the type it serves (bfloat16) and the plain reference draws the very
same values again from the seed, one layer at a time, so it takes nothing
the program made. Every leaf is a float32 normal draw from its own key,
scaled, then rounded to the served type; the reference reads the rounded
values back in float32.

Scales keep a deep random network well conditioned: unit-RMS embeddings,
projections of 1/sqrt(fan_in), residual-branch outputs cut by 1/sqrt(2L),
norm scales 1 + 0.1 N(0, 1) and qkv biases 0.1 N(0, 1).

``spec`` is the configuration's JSON object (Hugging Face key names).
Imports nothing of the program."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# fixed sub-key index of every leaf of one layer
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                "q_norm", "k_norm", "wg", "wi", "wd")


def dims(spec):
    d = spec["hidden_size"]
    H = spec["num_attention_heads"]
    return dict(d=d, H=H, K=spec["num_key_value_heads"],
                D=spec.get("head_dim") or d // H,
                F=spec["intermediate_size"], L=spec["num_hidden_layers"],
                V=spec["vocab_size"])


def served(spec, key):
    """The value of ``key`` as the program serves it: a key the file lists
    under ``differs`` (the program departs from the published value) is
    served as its ``served`` entry."""
    d = spec.get("differs", {}).get(key)
    return d["served"] if d else spec[key]


def root_key(seed: int):
    """A JAX key from any whole number (the harness's seeds pass 2**31)."""
    s = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(1)[0]
    return jax.random.key(int(s))


def _keys(root):
    k = root
    return {n: jax.random.fold_in(k, i)
            for i, n in enumerate(("embed", "head", "final", "layers"))}


def _normal(key, shape, scale, shift=0.0):
    return jax.random.normal(key, shape, jnp.float32) * scale + shift


def layer_f32(spec, key):
    """One layer's leaves as float32 draws (not yet rounded)."""
    m = dims(spec)
    d, H, K, D, F, L = m["d"], m["H"], m["K"], m["D"], m["F"], m["L"]
    ks = {n: jax.random.fold_in(key, i) for i, n in enumerate(LAYER_LEAVES)}
    branch = 1.0 / math.sqrt(2 * L)
    w = {"ln1": _normal(ks["ln1"], (d,), 0.1, 1.0),
         "ln2": _normal(ks["ln2"], (d,), 0.1, 1.0),
         "wq": _normal(ks["wq"], (d, H * D), d ** -0.5),
         "wk": _normal(ks["wk"], (d, K * D), d ** -0.5),
         "wv": _normal(ks["wv"], (d, K * D), d ** -0.5),
         "wo": _normal(ks["wo"], (H * D, d), (H * D) ** -0.5 * branch),
         "wg": _normal(ks["wg"], (d, F), d ** -0.5),
         "wi": _normal(ks["wi"], (d, F), d ** -0.5),
         "wd": _normal(ks["wd"], (F, d), F ** -0.5 * branch)}
    if spec.get("qkv_bias"):
        w["bq"] = _normal(ks["bq"], (H * D,), 0.1)
        w["bk"] = _normal(ks["bk"], (K * D,), 0.1)
        w["bv"] = _normal(ks["bv"], (K * D,), 0.1)
    if spec.get("qk_norm"):
        w["q_norm"] = _normal(ks["q_norm"], (D,), 0.1, 1.0)
        w["k_norm"] = _normal(ks["k_norm"], (D,), 0.1, 1.0)
    return w


def _rounded(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


# --------------------------------------------------------------- reference
def reference_layer(spec, root, i, dtype=jnp.bfloat16):
    """Layer ``i`` in float32, rounded through the served ``dtype``."""
    key = jax.random.fold_in(_keys(root)["layers"], i)
    return _rounded(_rounded(layer_f32(spec, key), dtype), jnp.float32)


def reference_outer(spec, root, dtype=jnp.bfloat16):
    """Embedding [V, d], final norm [d] and output head [d, V] in float32,
    rounded through the served ``dtype``."""
    m = dims(spec)
    k = _keys(root)
    w = {"embed": _normal(k["embed"], (m["V"], m["d"]), 1.0),
         "final": _normal(k["final"], (m["d"],), 0.1, 1.0),
         "head": _normal(k["head"], (m["d"], m["V"]), m["d"] ** -0.5)}
    return _rounded(_rounded(w, dtype), jnp.float32)


# ----------------------------------------------------------------- program
def program_tree(spec, root, groups: int, dtype=jnp.bfloat16):
    """The served path's parameter tree (``repro`` layout: vocab split in
    ``groups`` shards, layers stacked under ``body.s0``), a pure function of
    the root key (``root_key(seed)``) for ``jax.jit`` with the model's
    out_shardings."""
    m = dims(spec)
    k = _keys(root)
    V, d, G = m["V"], m["d"], groups
    assert V % G == 0, (V, G)
    emb = _normal(k["embed"], (V, d), 1.0).astype(dtype)
    head = _normal(k["head"], (d, V), d ** -0.5).astype(dtype)
    lk = k["layers"]
    lay = jax.lax.map(
        lambda i: _rounded(layer_f32(spec, jax.random.fold_in(lk, i)), dtype),
        jnp.arange(m["L"]))
    attn = {n: lay[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv",
                                "q_norm", "k_norm") if n in lay}
    tree = {"embed": {"table": emb.reshape(G, V // G, d)},
            "final_norm": {"scale": _normal(k["final"], (d,), 0.1,
                                            1.0).astype(dtype)},
            "prefix": {}, "suffix": {},
            "body": {"s0": {"ln1": {"scale": lay["ln1"]}, "attn": attn,
                            "ln2": {"scale": lay["ln2"]},
                            "ffn": {"wi": lay["wi"], "wg": lay["wg"],
                                    "wo": lay["wd"]}}}}
    if not served(spec, "tie_word_embeddings"):
        tree["lm_head"] = {"w": head.reshape(d, G, V // G).transpose(1, 0, 2)}
    return tree
