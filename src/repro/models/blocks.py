"""Per-kind transformer blocks (pre-norm residual), dispatching to the
attention / MLA / MoE / RG-LRU / SSD sublayers. One (init, specs, apply_*)
triple per layer kind; ``transformer.py`` stacks them by the config's
layer pattern."""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.parallel import Layout
from . import attention as A
from . import mla as M
from . import moe as MOE
from . import recurrent as R
from . import ssd as S
from .layers import (norm_init, apply_norm, mlp_init, mlp_specs, mlp_apply)


def _use_mla(cfg):
    return cfg.mla is not None


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------
def block_init(key, kind, cfg, lay: Layout, dtype, pod_scale=False):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {"ln1": norm_init(cfg.norm, d, dtype)}
    if kind in ("attn", "local", "moe", "enc", "dec"):
        p["attn"] = (M.mla_init(ks[0], cfg, lay, dtype) if _use_mla(cfg)
                     else A.attn_init(ks[0], cfg, lay, dtype))
        p["ln2"] = norm_init(cfg.norm, d, dtype)
        if kind == "moe":
            p["ffn"] = MOE.moe_init(ks[1], cfg, lay, dtype, pod_scale)
        else:
            p["ffn"] = mlp_init(ks[1], d, cfg.d_ff, cfg.act, lay, dtype)
        if kind == "dec":
            p["lnx"] = norm_init(cfg.norm, d, dtype)
            p["cross"] = A.attn_init(ks[2], cfg, lay, dtype)
    elif kind == "rglru":
        p["mix"] = R.rglru_init(ks[0], cfg, lay, dtype)
        p["ln2"] = norm_init(cfg.norm, d, dtype)
        p["ffn"] = mlp_init(ks[1], d, cfg.d_ff, cfg.act, lay, dtype)
    elif kind == "ssd":
        p["mix"] = S.ssd_init(ks[0], cfg, lay, dtype)
    else:
        raise ValueError(kind)
    return p


def block_specs(kind, cfg, lay: Layout, pod_scale=False):
    n = {"scale": P(None)} if cfg.norm == "rmsnorm" else {"scale": P(None), "bias": P(None)}
    s = {"ln1": dict(n)}
    if kind in ("attn", "local", "moe", "enc", "dec"):
        s["attn"] = (M.mla_specs(cfg, lay) if _use_mla(cfg)
                     else A.attn_specs(cfg, lay))
        s["ln2"] = dict(n)
        s["ffn"] = (MOE.moe_specs(cfg, lay, pod_scale) if kind == "moe"
                    else mlp_specs(cfg.act, lay))
        if kind == "dec":
            s["lnx"] = dict(n)
            s["cross"] = A.attn_specs(cfg, lay)
    elif kind == "rglru":
        s["mix"] = R.rglru_specs(cfg, lay)
        s["ln2"] = dict(n)
        s["ffn"] = mlp_specs(cfg.act, lay)
    elif kind == "ssd":
        s["mix"] = S.ssd_specs(cfg, lay)
    return s


def block_cache_init(kind, cfg, lay: Layout, batch: int, s_max: int, dtype):
    if kind in ("attn", "moe"):
        if _use_mla(cfg):
            return M.mla_cache_init(cfg, lay, batch, s_max, dtype)
        return A.cache_init(cfg, lay, batch, s_max, dtype)
    if kind == "local":
        return A.cache_init(cfg, lay, batch, min(s_max, cfg.local_window), dtype)
    if kind == "dec":
        c = A.cache_init(cfg, lay, batch, s_max, dtype)
        x = A.cache_init(cfg, lay, batch, cfg.encoder_seq, dtype)
        return {"self": c, "cross": x}
    if kind == "rglru":
        return R.rglru_state_init(cfg, lay, batch, dtype)
    if kind == "ssd":
        return S.ssd_state_init(cfg, lay, batch, dtype)
    if kind == "enc":
        return {}
    raise ValueError(kind)


def block_paged_cache_init(kind, cfg, lay: Layout, num_blocks: int,
                           block_size: int, dtype):
    """Paged pool for one block. Only plain GQA attention layers page; the
    other kinds either keep per-sequence recurrent state (rglru/ssd), a
    latent layout (MLA), or a ring buffer (local) — the engine falls back
    to the contiguous cache for configs containing them."""
    if kind in ("attn", "moe") and not _use_mla(cfg):
        return A.paged_cache_init(cfg, lay, num_blocks, block_size, dtype)
    raise ValueError(f"layer kind {kind!r} does not support a paged cache")


def block_paged_cache_specs(kind, cfg, lay: Layout):
    if kind in ("attn", "moe") and not _use_mla(cfg):
        return A.paged_cache_specs(lay)
    raise ValueError(f"layer kind {kind!r} does not support a paged cache")


def block_cache_specs(kind, cfg, lay: Layout):
    if kind in ("attn", "moe"):
        if _use_mla(cfg):
            return M.mla_cache_specs(lay)
        return A.cache_specs(lay)
    if kind == "local":
        return A.cache_specs(lay)
    if kind == "dec":
        return {"self": A.cache_specs(lay), "cross": A.cache_specs(lay)}
    if kind == "rglru":
        return R.rglru_state_specs(lay)
    if kind == "ssd":
        return S.ssd_state_specs(lay)
    if kind == "enc":
        return {}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------
def block_prefill(p, kind, x, cache, ctx, cfg, lay: Layout, pod_scale=False,
                  train=False):
    """x: [B, S_loc, d]. ctx: dict(offsets, enc_out, ...).
    Returns (x, cache, aux)."""
    aux = 0.0
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    offsets = ctx["offsets"]
    if kind in ("attn", "moe"):
        if _use_mla(cfg):
            a, cache = M.mla_prefill(p["attn"], h, cache, offsets, cfg, lay)
        elif ctx.get("q_lens") is not None:
            a, cache = A.paged_attn_mixed(p["attn"], h, cache, offsets,
                                          ctx["q_lens"],
                                          ctx["block_tables"], cfg, lay,
                                          kcfg=ctx.get("kcfg"),
                                          layer=ctx.get("layer"))
        elif ctx.get("block_tables") is not None:
            a, cache = A.paged_attn_prefill(p["attn"], h, cache, offsets,
                                            ctx["block_tables"], cfg, lay,
                                            kcfg=ctx.get("kcfg"),
                                            layer=ctx.get("layer"))
        else:
            a, cache = A.attn_prefill(p["attn"], h, cache, offsets, cfg, lay)
        x = x + a
    elif kind == "local":
        a, cache = A.attn_prefill(p["attn"], h, cache, offsets, cfg, lay,
                                  window=cfg.local_window)
        x = x + a
    elif kind == "enc":
        a, _ = A.attn_prefill(p["attn"], h, None, offsets, cfg, lay,
                              rope=False, causal=False)
        x = x + a
    elif kind == "dec":
        a, sc = A.attn_prefill(p["attn"], h,
                               cache["self"] if cache else None, offsets,
                               cfg, lay, rope=False)
        x = x + a
        hx = apply_norm(cfg.norm, p["lnx"], x, cfg.norm_eps)
        if cache is None or ctx.get("init_cross", False):
            cross = A.cross_kv_prefill(p["cross"], ctx["enc_out"], cfg, lay)
        else:
            cross = cache["cross"]
        cache = {"self": sc, "cross": cross} if cache is not None else None
        x = x + A.cross_attend(p["cross"], hx, cross, cfg, lay)
    elif kind == "rglru":
        a, cache = R.rglru_prefill(p["mix"], h, cache, cfg, lay)
        x = x + a
    elif kind == "ssd":
        a, cache = S.ssd_prefill(p["mix"], h, cache, cfg, lay)
        return x + a, cache, aux
    # FFN half
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        f, aux = MOE.moe_apply(p["ffn"], h2, cfg, lay, pod_scale, train=train)
    else:
        f = mlp_apply(p["ffn"], h2, cfg.act, lay)
    return x + f, cache, aux


def block_decode(p, kind, x, cache, ctx, cfg, lay: Layout, pod_scale=False):
    """x: [B_loc, d] (decode batch sharded over sp). Returns (x, cache)."""
    h = apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    lens = ctx["lens"]
    if kind in ("attn", "moe"):
        if _use_mla(cfg):
            a, cache = M.mla_decode(p["attn"], h, cache, lens, cfg, lay)
        elif ctx.get("block_tables") is not None:
            a, cache = A.paged_attn_decode(p["attn"], h, cache, lens,
                                           ctx["block_tables"], cfg, lay,
                                           kcfg=ctx.get("kcfg"),
                                           layer=ctx.get("layer"))
        else:
            a, cache = A.attn_decode(p["attn"], h, cache, lens, cfg, lay)
        x = x + a
    elif kind == "local":
        a, cache = A.attn_decode(p["attn"], h, cache, lens, cfg, lay,
                                 window=cfg.local_window)
        x = x + a
    elif kind == "dec":
        a, sc = A.attn_decode(p["attn"], h, cache["self"], lens, cfg, lay,
                              rope=False)
        x = x + a
        cache = {"self": sc, "cross": cache["cross"]}
        hx = apply_norm(cfg.norm, p["lnx"], x, cfg.norm_eps)
        x = x + A.cross_attend(p["cross"], hx, cache["cross"], cfg, lay,
                               decode=True)
    elif kind == "rglru":
        a, cache = R.rglru_decode(p["mix"], h, cache, cfg, lay)
        x = x + a
    elif kind == "ssd":
        a, cache = S.ssd_decode(p["mix"], h, cache, cfg, lay)
        return x + a, cache
    h2 = apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    if kind == "moe":
        f, _ = MOE.moe_apply(p["ffn"], h2[:, None, :], cfg, lay, pod_scale)
        f = f[:, 0]
    else:
        f = mlp_apply(p["ffn"], h2, cfg.act, lay)
    return x + f, cache
