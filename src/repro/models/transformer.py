"""Model assembly: stacks blocks per the config's layer pattern with
``lax.scan`` over pattern repeats (HLO stays O(1) in depth), builds caches,
and exposes the three step bodies (train / prefill / decode) that run inside
``shard_map``.

A stacked paged pool ``[L, N, Hkv, bs, Dh]`` rides in the scan's carry with
the layer index, and each layer addresses its blocks in the flat
``[L*N, ...]`` view at block ids offset by ``layer * N`` (see
``attention._pool_view``): the pool is written in place, never sliced per
layer or restacked. Other caches (contiguous, MLA latents, ring buffers,
recurrent state) are small per layer and stay the scan's ``xs``/``ys``."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel import Layout, psum_if, joint_axis_index
from . import blocks as BK
from .layers import (
    embed_init, embed_specs, embed_apply, lmhead_init, lmhead_specs,
    lmhead_apply, tied_lmhead_apply, norm_init, apply_norm,
    distributed_xent, distributed_argmax, dense_init)


def _sin_pos(positions, d):
    """Sinusoidal position embedding [..., d] (whisper-style frontends)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg, lay: Layout, dtype, pod_scale=False):
    ks = iter(jax.random.split(key, 16))
    p = {"embed": embed_init(next(ks), cfg.vocab_size, cfg.d_model, lay, dtype),
         "final_norm": norm_init(cfg.norm, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = lmhead_init(next(ks), cfg.d_model, cfg.vocab_size, lay, dtype)

    kinds = cfg.layer_kinds
    npre, nsuf = len(cfg.prefix_layers), len(cfg.suffix_layers)
    reps = cfg.pattern_repeats

    p["prefix"] = {str(i): BK.block_init(next(ks), kinds[i], cfg, lay, dtype, pod_scale)
                   for i in range(npre)}
    p["suffix"] = {str(i): BK.block_init(next(ks), kinds[npre + reps * len(cfg.layer_pattern) + i],
                                         cfg, lay, dtype, pod_scale)
                   for i in range(nsuf)}
    # stacked layers are drawn one at a time (lax.map, not vmap): a vmapped
    # draw is not partitioned under sharded out_shardings, so it would hold
    # every layer's float32 draw unsharded on each device at once
    body = {}
    for si, kind in enumerate(cfg.layer_pattern):
        kk = jax.random.split(next(ks), reps)
        body[f"s{si}"] = jax.lax.map(
            lambda k: BK.block_init(k, kind, cfg, lay, dtype, pod_scale), kk)
    p["body"] = body

    if cfg.encoder_layers:
        ek = jax.random.split(next(ks), cfg.encoder_layers)
        p["encoder"] = jax.lax.map(
            lambda k: BK.block_init(k, "enc", cfg, lay, dtype, pod_scale), ek)
        p["enc_norm"] = norm_init(cfg.norm, cfg.d_model, dtype)

    if cfg.mtp_depth:
        p["mtp"] = {
            "proj": dense_init(next(ks), (2 * cfg.d_model, cfg.d_model), dtype),
            "block": BK.block_init(next(ks), "attn", cfg, lay, dtype, pod_scale),
            "norm": norm_init(cfg.norm, cfg.d_model, dtype),
        }
    return p


def param_specs(cfg, lay: Layout, pod_scale=False):
    s = {"embed": embed_specs(lay),
         "final_norm": {k: P(None) for k in ({"scale"} if cfg.norm == "rmsnorm"
                                             else {"scale", "bias"})}}
    if not cfg.tie_embeddings:
        s["lm_head"] = lmhead_specs(lay)
    kinds = cfg.layer_kinds
    npre, nsuf = len(cfg.prefix_layers), len(cfg.suffix_layers)
    reps = cfg.pattern_repeats
    s["prefix"] = {str(i): BK.block_specs(kinds[i], cfg, lay, pod_scale)
                   for i in range(npre)}
    s["suffix"] = {str(i): BK.block_specs(kinds[npre + reps * len(cfg.layer_pattern) + i],
                                          cfg, lay, pod_scale)
                   for i in range(nsuf)}
    s["body"] = {
        f"s{si}": jax.tree.map(lambda sp: P(None, *sp),
                               BK.block_specs(kind, cfg, lay, pod_scale),
                               is_leaf=lambda x: isinstance(x, P))
        for si, kind in enumerate(cfg.layer_pattern)}
    if cfg.encoder_layers:
        s["encoder"] = jax.tree.map(lambda sp: P(None, *sp),
                                    BK.block_specs("enc", cfg, lay, pod_scale),
                                    is_leaf=lambda x: isinstance(x, P))
        s["enc_norm"] = dict(s["final_norm"])
    if cfg.mtp_depth:
        s["mtp"] = {"proj": P(None, None),
                    "block": BK.block_specs("attn", cfg, lay, pod_scale),
                    "norm": dict(s["final_norm"])}
    return s


def init_cache(cfg, lay: Layout, batch: int, s_max: int, dtype):
    kinds = cfg.layer_kinds
    npre, nsuf = len(cfg.prefix_layers), len(cfg.suffix_layers)
    reps = cfg.pattern_repeats
    c = {"prefix": {str(i): BK.block_cache_init(kinds[i], cfg, lay, batch, s_max, dtype)
                    for i in range(npre)},
         "suffix": {str(i): BK.block_cache_init(
             kinds[npre + reps * len(cfg.layer_pattern) + i], cfg, lay, batch,
             s_max, dtype) for i in range(nsuf)}}
    body = {}
    for si, kind in enumerate(cfg.layer_pattern):
        one = BK.block_cache_init(kind, cfg, lay, batch, s_max, dtype)
        body[f"s{si}"] = jax.tree.map(
            lambda a: jnp.zeros((reps,) + a.shape, a.dtype), one)
    c["body"] = body
    return c


def init_paged_cache(cfg, lay: Layout, num_blocks: int, block_size: int,
                     dtype):
    """Paged KV pools, one per cached layer (``num_blocks`` blocks per dp
    row — see ``attention.paged_cache_init``), same tree structure as
    ``init_cache``. All layers share the block-table indirection (a block
    maps the same token span in every layer), so one allocator per dp row
    serves the whole stack."""
    kinds = cfg.layer_kinds
    npre, nsuf = len(cfg.prefix_layers), len(cfg.suffix_layers)
    reps = cfg.pattern_repeats
    c = {"prefix": {str(i): BK.block_paged_cache_init(
            kinds[i], cfg, lay, num_blocks, block_size, dtype)
            for i in range(npre)},
         "suffix": {str(i): BK.block_paged_cache_init(
             kinds[npre + reps * len(cfg.layer_pattern) + i], cfg, lay,
             num_blocks, block_size, dtype) for i in range(nsuf)}}
    body = {}
    for si, kind in enumerate(cfg.layer_pattern):
        one = BK.block_paged_cache_init(kind, cfg, lay, num_blocks,
                                        block_size, dtype)
        body[f"s{si}"] = jax.tree.map(
            lambda a: jnp.zeros((reps,) + a.shape, a.dtype), one)
    c["body"] = body
    return c


def paged_cache_specs(cfg, lay: Layout):
    kinds = cfg.layer_kinds
    npre, nsuf = len(cfg.prefix_layers), len(cfg.suffix_layers)
    reps = cfg.pattern_repeats
    s = {"prefix": {str(i): BK.block_paged_cache_specs(kinds[i], cfg, lay)
                    for i in range(npre)},
         "suffix": {str(i): BK.block_paged_cache_specs(
             kinds[npre + reps * len(cfg.layer_pattern) + i], cfg, lay)
             for i in range(nsuf)}}
    s["body"] = {
        f"s{si}": jax.tree.map(lambda sp: P(None, *sp),
                               BK.block_paged_cache_specs(kind, cfg, lay),
                               is_leaf=lambda x: isinstance(x, P))
        for si, kind in enumerate(cfg.layer_pattern)}
    return s


def cache_specs(cfg, lay: Layout):
    kinds = cfg.layer_kinds
    npre, nsuf = len(cfg.prefix_layers), len(cfg.suffix_layers)
    reps = cfg.pattern_repeats
    s = {"prefix": {str(i): BK.block_cache_specs(kinds[i], cfg, lay)
                    for i in range(npre)},
         "suffix": {str(i): BK.block_cache_specs(
             kinds[npre + reps * len(cfg.layer_pattern) + i], cfg, lay)
             for i in range(nsuf)}}
    s["body"] = {
        f"s{si}": jax.tree.map(lambda sp: P(None, *sp),
                               BK.block_cache_specs(kind, cfg, lay),
                               is_leaf=lambda x: isinstance(x, P))
        for si, kind in enumerate(cfg.layer_pattern)}
    return s


# ---------------------------------------------------------------------------
# embedding frontends
# ---------------------------------------------------------------------------
def _embed_tokens(params, tokens, positions, cfg, lay, frontend_embeds=None):
    x = embed_apply(params["embed"], tokens, lay)
    if cfg.family == "audio":
        x = x + _sin_pos(positions, cfg.d_model).astype(x.dtype)
    if frontend_embeds is not None and cfg.frontend == "vision_stub":
        fs = cfg.frontend_seq
        idx = jnp.clip(positions, 0, fs - 1)[..., None]          # [B, S, 1]
        img = jnp.take_along_axis(frontend_embeds, idx, axis=1)  # [B, S, d]
        x = jnp.where((positions < fs)[..., None], img.astype(x.dtype), x)
    return x


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------
def _run_encoder(params, frames, cfg, lay):
    """frames: [B, S_enc_loc, d] (stub audio embeddings, seq-sharded)."""
    r = joint_axis_index(lay.sp_axes, dict(lay.axis_sizes)) if lay.sp > 1 else 0
    S_loc = frames.shape[1]
    pos = r * S_loc + jnp.arange(S_loc)[None, :]
    x = frames + _sin_pos(jnp.broadcast_to(pos, frames.shape[:2]),
                          cfg.d_model).astype(frames.dtype)
    ctx = {"offsets": jnp.zeros((frames.shape[0],), jnp.int32)}

    def body(xc, pb):
        y, _, _ = BK.block_prefill(pb, "enc", xc, {}, ctx, cfg, lay)
        return y, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return apply_norm(cfg.norm, params["enc_norm"], x, cfg.norm_eps)


def _paged(cache, ctx) -> bool:
    """Whether the step runs against the paged pool: then every cached
    layer holds one (``Model.supports_paged``)."""
    return cache is not None and ctx.get("block_tables") is not None


def _run_blocks_prefill(params, cache, x, ctx, cfg, lay, pod_scale, train,
                        remat=False):
    kinds = cfg.layer_kinds
    npre = len(cfg.prefix_layers)
    reps = cfg.pattern_repeats
    aux = jnp.zeros((), jnp.float32)
    newc = {"prefix": {}, "suffix": {}, "body": {}}
    for i in range(npre):
        x, c, a = BK.block_prefill(params["prefix"][str(i)], kinds[i], x,
                                   cache["prefix"][str(i)] if cache else None,
                                   ctx, cfg, lay, pod_scale, train)
        newc["prefix"][str(i)] = c
        aux += a

    paged = _paged(cache, ctx)

    def sb(carry, xs):
        # a paged pool rides in the carry, with the layer index ``i``;
        # other caches are the scan's xs/ys
        (xc, auxc), i, pools = carry
        pb, cb = xs
        lctx, cb = (dict(ctx, layer=i), pools) if paged else (ctx, cb)
        out_cb = {}
        for si, kind in enumerate(cfg.layer_pattern):
            xc, c, a = BK.block_prefill(pb[f"s{si}"], kind, xc,
                                        cb[f"s{si}"] if cb is not None else None,
                                        lctx, cfg, lay, pod_scale, train)
            out_cb[f"s{si}"] = c if c is not None else jnp.zeros((), jnp.int32)
            auxc = auxc + a
        if paged:
            return ((xc, auxc), i + 1, out_cb), None
        return ((xc, auxc), i + 1, None), out_cb

    if reps:
        fn = jax.checkpoint(sb) if remat else sb
        ((x, aux), _, pools), body_c = jax.lax.scan(
            fn, ((x, aux), jnp.int32(0), cache["body"] if paged else None),
            (params["body"], cache["body"] if cache and not paged else None))
        newc["body"] = pools if paged else body_c
    nsuf = len(cfg.suffix_layers)
    off = npre + reps * len(cfg.layer_pattern)
    for i in range(nsuf):
        x, c, a = BK.block_prefill(params["suffix"][str(i)], kinds[off + i], x,
                                   cache["suffix"][str(i)] if cache else None,
                                   ctx, cfg, lay, pod_scale, train)
        newc["suffix"][str(i)] = c
        aux += a
    return x, (newc if cache else None), aux


def _run_blocks_decode(params, cache, x, ctx, cfg, lay, pod_scale):
    kinds = cfg.layer_kinds
    npre = len(cfg.prefix_layers)
    reps = cfg.pattern_repeats
    newc = {"prefix": {}, "suffix": {}, "body": {}}
    for i in range(npre):
        x, c = BK.block_decode(params["prefix"][str(i)], kinds[i], x,
                               cache["prefix"][str(i)], ctx, cfg, lay, pod_scale)
        newc["prefix"][str(i)] = c

    paged = _paged(cache, ctx)

    def sb(carry, xs):
        xc, i, pools = carry                       # as in _run_blocks_prefill
        pb, cb = xs
        lctx, cb = (dict(ctx, layer=i), pools) if paged else (ctx, cb)
        out_cb = {}
        for si, kind in enumerate(cfg.layer_pattern):
            xc, out_cb[f"s{si}"] = BK.block_decode(pb[f"s{si}"], kind, xc,
                                                   cb[f"s{si}"], lctx, cfg,
                                                   lay, pod_scale)
        if paged:
            return (xc, i + 1, out_cb), None
        return (xc, i + 1, None), out_cb

    if reps:
        (x, _, pools), body_c = jax.lax.scan(
            sb, (x, jnp.int32(0), cache["body"] if paged else None),
            (params["body"], None if paged else cache["body"]))
        newc["body"] = pools if paged else body_c
    nsuf = len(cfg.suffix_layers)
    off = npre + reps * len(cfg.layer_pattern)
    for i in range(nsuf):
        x, c = BK.block_decode(params["suffix"][str(i)], kinds[off + i], x,
                               cache["suffix"][str(i)], ctx, cfg, lay, pod_scale)
        newc["suffix"][str(i)] = c
    return x, newc


# ---------------------------------------------------------------------------
# step bodies (run inside shard_map)
# ---------------------------------------------------------------------------
def _positions_prefill(tokens, offsets, lay):
    B, S_loc = tokens.shape
    r = joint_axis_index(lay.sp_axes, dict(lay.axis_sizes)) if lay.sp > 1 else 0
    return offsets[:, None] + r * S_loc + jnp.arange(S_loc)[None, :]


def prefill_body(params, cache, tokens, offsets, cfg, lay: Layout,
                 pod_scale=False, frontend_embeds=None, enc_frames=None,
                 block_tables=None, kcfg=None):
    """tokens: [B, S_loc]; offsets: [B]. Returns (last_logits_loc [B, v_loc],
    cache). With ``block_tables`` [B, nmax] the cache is the paged pool
    and ``kcfg`` (KernelConfig) selects the paged-attention backend."""
    pos = _positions_prefill(tokens, offsets, lay)
    x = _embed_tokens(params, tokens, pos, cfg, lay, frontend_embeds)
    ctx = {"offsets": offsets, "init_cross": True,
           "block_tables": block_tables, "kcfg": kcfg}
    if cfg.encoder_layers:
        ctx["enc_out"] = _run_encoder(params, enc_frames, cfg, lay)
    x, cache, _ = _run_blocks_prefill(params, cache, x, ctx, cfg, lay,
                                      pod_scale, train=False)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    last = x[:, -1, :]
    if lay.sp > 1:
        r = joint_axis_index(lay.sp_axes, dict(lay.axis_sizes))
        last = jax.lax.psum(
            jnp.where(r == lay.sp - 1, last, jnp.zeros_like(last)), lay.sp_axes)
    logits = (tied_lmhead_apply(params["embed"], last, lay) if cfg.tie_embeddings
              else lmhead_apply(params["lm_head"], last, lay))
    return logits, cache


def mixed_body(params, cache, tokens, q_lens, offsets, cfg, lay: Layout,
               pod_scale=False, frontend_embeds=None, block_tables=None,
               sample=True, kcfg=None, n_last=1):
    """Unified mixed prefill+decode step against the paged pool.

    tokens: [B, S_loc] — row b carries ``q_lens[b]`` fresh tokens written
    at cache positions ``offsets[b] ..``; decode rows have q_len == 1
    (plus up to k speculative draft tokens when the engine is verifying),
    chunked-prefill rows up to the chunk width, padding rows 0. Returns
    (next_token [B] greedy — or last-token logits [B, v_loc] with
    ``sample=False`` — and the updated pool). Rows whose chunk does not
    reach the end of their known tokens get a garbage next_token the
    engine ignores.

    ``n_last`` (static) is the speculative verify width: with n_last > 1
    the ragged extraction takes the last ``n_last`` query positions of
    each row instead of the single newest one, returning [B, n_last]
    tokens (or [B, n_last, v_loc] logits). Row b's output j corresponds
    to global column ``q_lens[b] - n_last + j``; columns before the
    row's start are masked to zero logits and their outputs are garbage
    the engine ignores. n_last == 1 is bit-for-bit the original
    single-token path."""
    pos = _positions_prefill(tokens, offsets, lay)
    x = _embed_tokens(params, tokens, pos, cfg, lay, frontend_embeds)
    ctx = {"offsets": offsets, "q_lens": q_lens, "block_tables": block_tables,
           "kcfg": kcfg}
    x, cache, _ = _run_blocks_prefill(params, cache, x, ctx, cfg, lay,
                                      pod_scale, train=False)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    # ragged last-token extraction: row b's newest token sits at global
    # column q_lens[b]-1, which lives on exactly one sp rank
    B, S_loc = x.shape[:2]
    r = joint_axis_index(lay.sp_axes, dict(lay.axis_sizes)) if lay.sp > 1 else 0
    if n_last == 1:
        loc = q_lens - 1 - r * S_loc                           # [B] local col
        here = (loc >= 0) & (loc < S_loc)
        take = jnp.take_along_axis(
            x, jnp.clip(loc, 0, S_loc - 1)[:, None, None], axis=1)[:, 0]
        last = jnp.where(here[:, None], take, jnp.zeros_like(take))
    else:
        # ragged last-k: global columns q_lens[b]-n_last .. q_lens[b]-1,
        # each living on exactly one sp rank; columns < 0 masked
        cols = q_lens[:, None] - n_last + jnp.arange(n_last)[None, :]
        loc = cols - r * S_loc                                 # [B, n_last]
        here = (loc >= 0) & (loc < S_loc) & (cols >= 0)
        take = jnp.take_along_axis(
            x, jnp.clip(loc, 0, S_loc - 1)[:, :, None], axis=1)
        last = jnp.where(here[:, :, None], take, jnp.zeros_like(take))
    if lay.sp > 1:
        last = jax.lax.psum(last, lay.sp_axes)
    logits = (tied_lmhead_apply(params["embed"], last, lay) if cfg.tie_embeddings
              else lmhead_apply(params["lm_head"], last, lay))
    if sample:
        return distributed_argmax(logits, lay), cache
    return logits, cache


def decode_body(params, cache, tokens, lens, cfg, lay: Layout, pod_scale=False,
                block_tables=None, kcfg=None):
    """tokens: [B_loc] (batch sharded over dp×sp); lens: [B_row] global
    per-sequence lengths within this dp row. Returns (logits [B_loc, v_loc],
    cache). With ``block_tables`` [B, nmax] the cache is the paged pool."""
    x = embed_apply(params["embed"], tokens, lay)
    if cfg.family == "audio":
        r = joint_axis_index(lay.sp_axes, dict(lay.axis_sizes)) if lay.sp > 1 else 0
        B_loc = tokens.shape[0]
        pos_loc = jax.lax.dynamic_slice(lens, (r * B_loc,), (B_loc,)) if lay.sp > 1 else lens
        x = x + _sin_pos(pos_loc, cfg.d_model).astype(x.dtype)
    ctx = {"lens": lens, "block_tables": block_tables, "kcfg": kcfg}
    x, cache = _run_blocks_decode(params, cache, x, ctx, cfg, lay, pod_scale)
    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = (tied_lmhead_apply(params["embed"], x, lay) if cfg.tie_embeddings
              else lmhead_apply(params["lm_head"], x, lay))
    return logits, cache


def greedy_body(logits, lay: Layout):
    """Distributed greedy sampling; returns [B_row] token ids (replicated)."""
    tok = distributed_argmax(logits, lay)
    if lay.sp > 1:
        tok = jax.lax.all_gather(tok, lay.sp_axes, axis=0, tiled=True)
    return tok


def loss_body(params, tokens, labels, cfg, lay: Layout, pod_scale=False,
              frontend_embeds=None, enc_frames=None, remat=True):
    """Training loss (mean nll + aux). tokens/labels: [B_loc, S_loc]."""
    offsets = jnp.zeros((tokens.shape[0],), jnp.int32)
    pos = _positions_prefill(tokens, offsets, lay)
    x = _embed_tokens(params, tokens, pos, cfg, lay, frontend_embeds)
    ctx = {"offsets": offsets, "init_cross": True}
    if cfg.encoder_layers:
        ctx["enc_out"] = _run_encoder(params, enc_frames, cfg, lay)
    x, _, aux = _run_blocks_prefill(params, None, x, ctx, cfg, lay, pod_scale,
                                    train=True, remat=remat)
    h = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = (tied_lmhead_apply(params["embed"], h, lay) if cfg.tie_embeddings
              else lmhead_apply(params["lm_head"], h, lay))
    nll = distributed_xent(logits, labels, cfg.vocab_size, lay)
    valid = (labels >= 0).astype(jnp.float32)
    loss_sum = (nll * valid).sum()
    count = valid.sum()
    loss_sum = psum_if(loss_sum, lay.dp_axes + lay.sp_axes)
    count = psum_if(count, lay.dp_axes + lay.sp_axes)
    loss = loss_sum / jnp.maximum(count, 1.0)

    if cfg.mtp_depth and "mtp" in params:
        mp = params["mtp"]
        emb_next = embed_apply(params["embed"], jnp.maximum(labels, 0), lay)
        hin = jnp.concatenate(
            [apply_norm(cfg.norm, mp["norm"], x, cfg.norm_eps), emb_next],
            axis=-1) @ mp["proj"]
        hm, _, _ = BK.block_prefill(mp["block"], "attn", hin, None, ctx, cfg,
                                    lay, pod_scale, train=True)
        hm = apply_norm(cfg.norm, params["final_norm"], hm, cfg.norm_eps)
        lg2 = (tied_lmhead_apply(params["embed"], hm, lay) if cfg.tie_embeddings
               else lmhead_apply(params["lm_head"], hm, lay))
        lab2 = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        nll2 = distributed_xent(lg2, lab2, cfg.vocab_size, lay)
        v2 = (lab2 >= 0).astype(jnp.float32)
        l2 = psum_if((nll2 * v2).sum(), lay.dp_axes + lay.sp_axes)
        c2 = psum_if(v2.sum(), lay.dp_axes + lay.sp_axes)
        loss = loss + 0.3 * l2 / jnp.maximum(c2, 1.0)

    aux = psum_if(aux, lay.dp_axes + lay.sp_axes) / max(lay.dp * lay.sp, 1)
    return loss + aux
