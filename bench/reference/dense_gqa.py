"""Plain float32 reference of a dense GQA decoder (Qwen2 / Qwen3 family).

Follows the published equations: pre-norm residual blocks with RMSNorm,
grouped-query causal attention with rotary embeddings (rotate-half form,
``theta ** (-2i / head_dim)``), optional qkv bias (Qwen2) and per-head
RMSNorm of queries and keys before the rotation (Qwen3), and a SwiGLU MLP
``down(silu(gate(x)) * up(x))``. The output head is the configuration's own
matrix, or the embedding when ``tie_word_embeddings`` is set.

It runs a whole sequence at once, layer by layer, drawing each layer's
weights from the seed (``bench.weights``) and dropping them after use, so
the 36-layer qwen3-8b fits one chip. Attention runs in blocks of queries.
Matrix products run at ``highest`` precision. ``control=True`` rounds
every matrix-product operand (weights per output column, activations per
row) to float8 e4m3 with its own scale: the lower precision that a
bfloat16 deployment would be tempted by.

Imports nothing of the program."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 512
ROW_BLOCK = 256
F8_MAX = 448.0


def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control):
    """x [..., k] @ w [k, n]."""
    if control:
        x, w = _f8(x, -1), _f8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [B, S, h, D], pos [S]."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]          # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, control):
    """Causal GQA. q [B, S, H, D]; k, v [B, S, K, D] -> [B, S, H, D]."""
    B, S, H, D = q.shape
    K = k.shape[2]
    g = H // K
    nq = S // Q_BLOCK
    qb = q.reshape(B, nq, Q_BLOCK, K, g, D).transpose(1, 0, 2, 3, 4, 5)
    if control:
        k, v = _f8(k, -1), _f8(v, -1)
    kpos = jnp.arange(S)

    def one(args):
        i, qi = args                                   # qi [B, Qb, K, g, D]
        if control:
            qi = _f8(qi, -1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k) * D ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if control:
            p = _f8(p, -1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    out = jax.lax.map(one, (jnp.arange(nq), qb))       # [nq, B, Qb, K, g, D]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, D)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _layer(w, x, cfg, control):
    eps, theta, qk_norm, D = cfg
    B, S, _ = x.shape
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], eps)
    q, k, v = (_mm(h, w["wq"], control), _mm(h, w["wk"], control),
               _mm(h, w["wv"], control))
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = q.reshape(B, S, -1, D)
    k = k.reshape(B, S, -1, D)
    v = v.reshape(B, S, -1, D)
    if qk_norm:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    a = _attend(q, k, v, control).reshape(B, S, -1)
    x = x + _mm(a, w["wo"], control)
    h = _rms(x, w["ln2"], eps)
    f = jax.nn.silu(_mm(h, w["wg"], control)) * _mm(h, w["wi"], control)
    return x + _mm(f, w["wd"], control)


@functools.partial(jax.jit, static_argnames=("control",))
def _logits(h, head, control):
    return _mm(h, head, control)


class Reference:
    """The reference for one configuration and seed."""

    def __init__(self, spec, seed):
        self.spec, self.seed = spec, seed
        self.m = W.dims(spec)
        self.cfg = (float(spec["rms_norm_eps"]), float(spec["rope_theta"]),
                    bool(spec.get("qk_norm")), self.m["D"])
        root = W.root_key(seed)
        layer = jax.jit(lambda k, i: W.reference_layer(spec, k, i))
        outer = jax.jit(lambda k: W.reference_outer(spec, k))
        self._layer_w = lambda i: layer(root, i)
        self._outer = lambda: outer(root)

    def hidden(self, tokens, control=False):
        """tokens [B, S] (S a multiple of Q_BLOCK) -> final-normed hidden
        states [B, S, d], float32."""
        with jax.default_matmul_precision("highest"):
            outer = self._outer()
            x = jnp.take(outer["embed"], jnp.asarray(tokens), axis=0)
            final = outer["final"]
            del outer
            for i in range(self.m["L"]):
                x = _layer(self._layer_w(i), x, self.cfg, control)
            eps = self.cfg[0]
            return _rms(x, final, eps)

    def head(self):
        o = self._outer()
        tied = W.served(self.spec, "tie_word_embeddings")
        return o["embed"].T if tied else o["head"]

    def served_gaps(self, seqs, n_served, control=False):
        """For each sequence (prompt + served tokens, the last ``n`` of them
        served), the reference's gap at every served position: its best
        logit minus its logit of the served token. With ``control`` also the
        gap of the token that the control precision puts first. Returns a
        list of float arrays (``(gap, control_gap)`` pairs with control)."""
        S = max(len(s) for s in seqs)
        S = -(-S // Q_BLOCK) * Q_BLOCK
        toks = np.zeros((len(seqs), S), np.int32)
        for b, s in enumerate(seqs):
            toks[b, :len(s)] = s
        out = []
        with jax.default_matmul_precision("highest"):
            h = self.hidden(toks)
            hc = self.hidden(toks, control=True) if control else None
            head = self.head()
            for b, (s, n) in enumerate(zip(seqs, n_served)):
                pos = np.arange(len(s) - n - 1, len(s) - 1)
                served = np.asarray(s[len(s) - n:], np.int32)
                gaps, cgaps = [], []
                for lo in range(0, n, ROW_BLOCK):
                    k = min(ROW_BLOCK, n - lo)
                    # rows padded to one block shape: one compile
                    p = np.pad(pos[lo:lo + k], (0, ROW_BLOCK - k), mode="edge")
                    tok = np.pad(served[lo:lo + k], (0, ROW_BLOCK - k),
                                 mode="edge")
                    lg = _logits(h[b, p], head, False)
                    best = jnp.max(lg, -1)
                    gaps.append(np.asarray(best - jnp.take_along_axis(
                        lg, jnp.asarray(tok)[:, None], -1)[:, 0])[:k])
                    if control:
                        c = jnp.argmax(_logits(hc[b, p], head, True), -1)
                        cgaps.append(np.asarray(best - jnp.take_along_axis(
                            lg, c[:, None], -1)[:, 0])[:k])
                g = np.concatenate(gaps) if gaps else np.zeros(0)
                out.append((g, np.concatenate(cgaps)) if control else g)
        return out
