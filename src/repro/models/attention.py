"""GQA attention under combined (SP, TP) — the attention half of the paper's
Algorithm 1, generalized for inference (GQA, KV replication, cache).

Runs inside ``shard_map``. The same code serves:
  base config : SP>1 — fused Ulysses a2a into head parallelism (lines 4-6)
  shift config: SP=1, TP=G — plain head parallelism over the joint group
  smoke       : all axes empty, single device

The KV cache local view is ``[B, S_max, kv_per_rank, Dh]``; its global
sharding ``P(dp, None, model_axes, None)`` is identical in base and shift
configs (KV-cache invariance)."""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel import HeadPlan, Layout, plan_heads, psum_if, joint_axis_index
from repro.core.ulysses import (
    ulysses_scatter_heads, ulysses_gather_heads, expand_kv_for_send)
from repro.kernels import ops as K
from .attention_math import attend, attend_partial, finish_partial
from .layers import dense_init, rmsnorm, apply_rope


def get_plan(cfg, lay: Layout) -> HeadPlan:
    return plan_heads(cfg.num_heads, cfg.num_kv_heads, max(lay.G, 1), max(lay.tp, 1))


def kv_exp_slots(plan: HeadPlan, lay: Layout) -> int:
    """KV head slots materialized in this layout's weights."""
    return max(plan.h_kv_pad, max(lay.tp, 1))


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def _place(canon, slot_map):
    """Scatter canonical per-head arrays into padded slot layout (axis=-2
    holds heads). Pad slots (orig == -1) become zeros. Deterministic in the
    canonical values, so every layout holds the same logical weights."""
    sm = jnp.asarray([max(s, 0) for s in slot_map])
    ok = jnp.asarray([1.0 if s >= 0 else 0.0 for s in slot_map], canon.dtype)
    out = jnp.take(canon, sm, axis=-2) * ok[:, None]
    return out


def attn_init(key, cfg, lay: Layout, dtype, prefix=""):
    plan = get_plan(cfg, lay)
    d, dh = cfg.d_model, cfg.head_dim
    kexp = kv_exp_slots(plan, lay)
    r = kexp // plan.h_kv_pad
    ks = jax.random.split(key, 8)
    # canonical per-real-head weights, placed into padded slots and
    # replicated into expanded kv slots -> all layouts share logical weights.
    wq_c = dense_init(ks[0], (d, cfg.num_heads, dh), dtype)
    wk_c = _place(dense_init(ks[1], (d, cfg.num_kv_heads, dh), dtype),
                  plan.kv_slot_to_orig)
    wv_c = _place(dense_init(ks[2], (d, cfg.num_kv_heads, dh), dtype),
                  plan.kv_slot_to_orig)
    wo_c = dense_init(ks[3], (cfg.num_heads, dh * d), dtype)
    p = {
        "wq": _place(wq_c, plan.q_slot_to_orig).reshape(d, plan.h_q_pad * dh),
        "wk": jnp.repeat(wk_c, r, axis=1).reshape(d, kexp * dh),
        "wv": jnp.repeat(wv_c, r, axis=1).reshape(d, kexp * dh),
        "wo": _place(wo_c[None], plan.q_slot_to_orig)[0].reshape(
            plan.h_q_pad * dh, d),
    }
    if cfg.qkv_bias:
        bq_c = dense_init(ks[4], (cfg.num_heads, dh), dtype, scale=0.02)
        bk_c = _place(dense_init(ks[5], (cfg.num_kv_heads, dh), dtype, scale=0.02),
                      plan.kv_slot_to_orig)
        bv_c = _place(dense_init(ks[6], (cfg.num_kv_heads, dh), dtype, scale=0.02),
                      plan.kv_slot_to_orig)
        p["bq"] = _place(bq_c, plan.q_slot_to_orig).reshape(plan.h_q_pad * dh)
        p["bk"] = jnp.repeat(bk_c, r, axis=0).reshape(kexp * dh)
        p["bv"] = jnp.repeat(bv_c, r, axis=0).reshape(kexp * dh)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def attn_specs(cfg, lay: Layout):
    tp = lay.tp_axes or None
    s = {"wq": P(None, tp), "wk": P(None, tp), "wv": P(None, tp),
         "wo": P(tp, None)}
    if cfg.qkv_bias:
        s.update({"bq": P(tp), "bk": P(tp), "bv": P(tp)})
    if cfg.qk_norm:
        s.update({"q_norm": P(None), "k_norm": P(None)})
    return s


def cache_init(cfg, lay: Layout, batch_global: int, s_max: int, dtype):
    """Global KV cache for one attention layer: [B, S_max, slots, Dh] with
    slots = G*kv_per_rank (replication materialized — same as TP GQA)."""
    plan = get_plan(cfg, lay)
    return {
        "k": jnp.zeros((batch_global, s_max, plan.kv_slots_total, cfg.head_dim), dtype),
        "v": jnp.zeros((batch_global, s_max, plan.kv_slots_total, cfg.head_dim), dtype),
    }


def cache_specs(lay: Layout):
    dp = lay.dp_axes or None
    h = lay.head_spec_entry()
    return {"k": P(dp, None, h, None), "v": P(dp, None, h, None)}


def paged_cache_init(cfg, lay: Layout, num_blocks: int, block_size: int,
                     dtype):
    """Physical KV block pool for one attention layer:
    ``[dp * num_blocks, slots, block_size, Dh]`` (head-major, so one head's
    block is the contiguous ``[block_size, Dh]`` tile the paged kernel
    reads) — ``num_blocks`` blocks
    PER dp row, concatenated on the leading axis, which is sharded over
    the dp mesh axes so each data-parallel row owns a private pool slice
    (inside ``shard_map`` a dp shard indexes its local ``[num_blocks, ...]``
    slice with row-local block ids straight from its block-table shard).

    The per-block layout is shard-invariant: only the head-slot axis is
    sharded over the tp-major *model* group (same as the contiguous
    cache), and the dp axes are identical in base and shift configs, so
    both map identical bytes of every block to identical devices and
    SP↔TP switching moves zero bytes. Each row's pool is shared across
    that row's sequences; ``block_tables`` assign physical blocks."""
    plan = get_plan(cfg, lay)
    shape = (max(lay.dp, 1) * num_blocks, plan.kv_slots_total, block_size,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_cache_specs(lay: Layout):
    dp = lay.dp_axes or None
    h = lay.head_spec_entry()
    return {"k": P(dp, h, None, None), "v": P(dp, h, None, None)}


def block_table_spec(lay: Layout) -> P:
    """Block tables are replicated across the model group (every rank
    follows the same logical→physical indirection)."""
    return P(lay.dp_axes or None, None)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def _tp_rank(lay: Layout):
    if not lay.tp_axes:
        return jnp.zeros((), jnp.int32)
    return joint_axis_index(lay.tp_axes, dict(lay.axis_sizes))


def _model_rank(lay: Layout):
    if not lay.model_axes:
        return jnp.zeros((), jnp.int32)
    return joint_axis_index(lay.model_axes, dict(lay.axis_sizes))


def _project_exchange(p, x, cfg, lay: Layout, plan: HeadPlan, src=None):
    """QKV projection (TP column parallel) + fused Ulysses exchange.

    x: [B, S_loc, d]. ``src`` overrides the KV input (cross-attention).
    Returns q [B, S, q_pr, dh], k, v [B, S, kv_pr, dh]  (S = full)."""
    dh = cfg.head_dim
    B, S_loc, _ = x.shape
    kv_in = src if src is not None else x
    q = x @ p["wq"]
    k = kv_in @ p["wk"]
    v = kv_in @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S_loc, -1, dh)
    k = k.reshape(B, kv_in.shape[1], -1, dh)
    v = v.reshape(B, kv_in.shape[1], -1, dh)
    if lay.sp > 1:
        k = expand_kv_for_send(k, plan, lay.sp, _tp_rank(lay))
        v = expand_kv_for_send(v, plan, lay.sp, _tp_rank(lay))
        q, k, v = ulysses_scatter_heads([q, k, v], lay)
    return q, k, v


def _finish(p, out, plan: HeadPlan, lay: Layout):
    """Mask padded head slots, gather heads back, O projection + TP psum
    (paper Alg. 1 lines 6-8)."""
    mask = jnp.asarray(plan.q_mask())
    g = _model_rank(lay)
    local = jax.lax.dynamic_slice(mask, (g * plan.q_per_rank,), (plan.q_per_rank,))
    out = out * local[None, None, :, None].astype(out.dtype)
    if lay.sp > 1:
        (out,) = ulysses_gather_heads([out], lay)
    B, S_loc = out.shape[:2]
    out = out.reshape(B, S_loc, -1)
    out = out @ p["wo"]
    return psum_if(out, lay.tp_axes)


def _qk_post(p, q, k, positions, cfg, rope: bool = True):
    if cfg.qk_norm:
        q = rmsnorm({"scale": p["q_norm"]}, q, cfg.norm_eps)
        k = rmsnorm({"scale": p["k_norm"]}, k, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


# ---------------------------------------------------------------------------
# prefill / train forward
# ---------------------------------------------------------------------------
def attn_prefill(p, x, cache, offsets, cfg, lay: Layout, *, window: int = 0,
                 rope: bool = True, causal: bool = True):
    """x: [B, S_loc, d] (seq sharded over sp); offsets: [B] cache offsets
    (zeros for training / plain prefill). Returns (out [B, S_loc, d], cache)."""
    plan = get_plan(cfg, lay)
    q, k, v = _project_exchange(p, x, cfg, lay, plan)
    B, S = q.shape[:2]
    pos = offsets[:, None] + jnp.arange(S)[None, :]            # [B, S] global
    q, k = _qk_post(p, q, k, pos, cfg, rope)

    if cache is not None:
        kc, vc = cache["k"], cache["v"]
        s_max = kc.shape[1]
        if window and s_max <= window:
            # Ring cache for sliding-window layers (long-context decode).
            # Attend over (old ring ++ fresh chunk); the old ring's slot j
            # holds global position  last_prev - ((wp_prev - j) mod s_max).
            last_prev = offsets[:, None] - 1                  # [B,1]
            wp_prev = (offsets[:, None] - 1) % s_max
            ring_pos = last_prev - ((wp_prev - jnp.arange(s_max)[None, :]) % s_max)
            ring_pos = jnp.where(ring_pos >= 0, ring_pos, -1)
            k_all = jnp.concatenate([kc, k], axis=1)
            v_all = jnp.concatenate([vc, v], axis=1)
            kv_pos = jnp.concatenate(
                [ring_pos, jnp.broadcast_to(pos, (B, S))], axis=1)
            out = attend(q, k_all, v_all, pos, kv_pos, causal=causal,
                         window=window, soft_cap=cfg.logits_soft_cap)
            n = min(S, s_max)
            psel = pos[:, -n:]
            kc = kc.at[jnp.arange(B)[:, None], psel % s_max].set(k[:, -n:])
            vc = vc.at[jnp.arange(B)[:, None], psel % s_max].set(v[:, -n:])
        else:
            def wr(c, new, off):
                return jax.lax.dynamic_update_slice(c, new, (off, 0, 0))
            kc = jax.vmap(wr)(kc, k, offsets)
            vc = jax.vmap(wr)(vc, v, offsets)
            kv_pos = jnp.arange(s_max)
            out = attend(q, kc, vc, pos, kv_pos, causal=causal, window=window,
                         kv_len=offsets + S, soft_cap=cfg.logits_soft_cap)
        cache = {"k": kc, "v": vc}
    else:
        kv_pos = jnp.arange(S)
        out = attend(q, k, v, pos, kv_pos, causal=causal, window=window,
                     soft_cap=cfg.logits_soft_cap)
    return _finish(p, out, plan, lay), cache


# ---------------------------------------------------------------------------
# decode forward (one token per sequence)
# ---------------------------------------------------------------------------
def attn_decode(p, x, cache, lens, cfg, lay: Layout, *, window: int = 0,
                rope: bool = True):
    """x: [B_loc, d] — decode batch sharded over sp (paper's load-balancing
    padding guarantees divisibility). lens: [B] global per-seq lengths.
    Returns (out [B_loc, d], cache)."""
    plan = get_plan(cfg, lay)
    xs = x[None]                                               # batch-as-seq
    q, k, v = _project_exchange(p, xs, cfg, lay, plan)
    B = q.shape[1]
    q = q.transpose(1, 0, 2, 3)                                # [B,1,q_pr,dh]
    k = k.transpose(1, 0, 2, 3)
    v = v.transpose(1, 0, 2, 3)
    pos = lens[:, None]                                        # [B,1]
    q, k = _qk_post(p, q, k, pos, cfg, rope)

    kc, vc = cache["k"], cache["v"]
    s_max = kc.shape[1]
    ring = bool(window) and s_max <= window
    wp = (lens % s_max) if ring else lens
    kc = kc.at[jnp.arange(B), wp].set(k[:, 0])
    vc = vc.at[jnp.arange(B), wp].set(v[:, 0])
    # Sq == 1: the direct (unchunked) partial path — the score tensor is
    # only [B, Hkv, g, 1, S_max] fp32, and it avoids the chunk-scan
    # transpose copies of the whole cache.
    if ring:
        kv_pos = lens[:, None] - ((wp[:, None] - jnp.arange(s_max)[None, :]) % s_max)
        acc, l, mm = attend_partial(q, kc, vc, pos, kv_pos, causal=True,
                                    window=window, soft_cap=cfg.logits_soft_cap)
    else:
        kv_pos = jnp.arange(s_max)
        acc, l, mm = attend_partial(q, kc, vc, pos, kv_pos, causal=True,
                                    window=window, kv_len=lens + 1,
                                    soft_cap=cfg.logits_soft_cap)
    out = finish_partial(acc, l, mm).astype(q.dtype)

    out = out.transpose(1, 0, 2, 3)                            # [1,B,q_pr,dh]
    out = _finish(p, out, plan, lay)                           # [1,B_loc,d]
    return out[0], {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# paged forward (block-table indirection; layouts as in paged_cache_init)
# ---------------------------------------------------------------------------
# The paged paths stream KV through the block table with the
# work-proportional ragged kernel (``kernels.ops.paged_ragged_attend``) —
# per-rank local heads are group-aligned by the planner, so the per-shard
# call inside shard_map sees [B, C, Hq_loc, Dh] queries against the local
# head-major [num_blocks, Hkv_loc, bs, Dh] pool slice. The old materialized gather
# (O(B·nmax·bs) per layer regardless of occupancy) survives only as the
# reference oracle in ``kernels.ref`` (KernelConfig(attn_backend="gather")).
#
# Inside the layer scan the pool arrives stacked, ``[L, N, Hkv, bs, Dh]``
# (the scan's carry), with the layer index ``layer``: the layer then writes
# and reads its blocks in the flat ``[L*N, Hkv, bs, Dh]`` view at block ids
# offset by ``layer * N``, so no step slices or restacks the pool.


def _pool_view(cache, layer):
    """(k, v, base): the pool as the flat block array the kernel indexes,
    and the block id of this layer's null block in it (``layer * N`` in a
    stacked pool; 0 in one layer's own pool, ``layer`` None). Merging the
    two leading dims of the (bs, Dh)-tiled array is a bitcast."""
    kc, vc = cache["k"], cache["v"]
    if layer is None:
        return kc, vc, 0
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
    return flat(kc), flat(vc), layer * kc.shape[1]


def _pool_back(cache, kc, vc):
    """The written flat view, in ``cache``'s own (possibly stacked) shape."""
    return {"k": kc.reshape(cache["k"].shape),
            "v": vc.reshape(cache["v"].shape)}


def paged_attn_mixed(p, x, cache, offsets, q_lens, block_tables, cfg,
                     lay: Layout, kcfg=None, layer=None):
    """Ragged mixed prefill+decode against the paged pool. x: [B, S_loc, d]
    where each row carries ``q_lens[b]`` fresh tokens starting at cache
    position ``offsets[b]`` (decode rows have q_len == 1, prefill rows up
    to the chunk width, padding rows 0). Columns past ``q_lens`` scatter
    into the null block and their outputs are garbage-but-finite (the
    caller discards them). ``kcfg``: KernelConfig selecting the attention
    backend; ``layer``: this layer's index in a stacked pool (see
    ``_pool_view``). Returns (out [B, S_loc, d], cache)."""
    plan = get_plan(cfg, lay)
    q, k, v = _project_exchange(p, x, cfg, lay, plan)
    B, S = q.shape[:2]
    pos = offsets[:, None] + jnp.arange(S)[None, :]            # [B, S] global
    q, k = _qk_post(p, q, k, pos, cfg, True)

    kc, vc, base = _pool_view(cache, layer)
    bs = kc.shape[2]
    nmax = block_tables.shape[1]
    # ragged scatter: only the first q_lens[b] columns are real tokens; the
    # rest (and any chunk overhang past the table when s_max % chunk != 0)
    # are routed to the null block EXPLICITLY — never through jnp's
    # version-dependent out-of-bounds gather default (clip would collide
    # the scatter with live KV).
    valid = (jnp.arange(S)[None, :] < q_lens[:, None]) & (pos // bs < nmax)
    blk = jnp.take_along_axis(block_tables,
                              jnp.minimum(pos // bs, nmax - 1), axis=1)
    blk = jnp.where(valid, blk, 0) + base                       # [B, S]
    # head-major pool: index (block, head, slot) takes k's [B, S, heads, Dh]
    heads = jnp.arange(kc.shape[1])[None, None, :]
    kc = kc.at[blk[..., None], heads, (pos % bs)[..., None]].set(k)
    vc = vc.at[blk[..., None], heads, (pos % bs)[..., None]].set(v)
    out = K.paged_ragged_attend(q, kc, vc, block_tables + base, q_lens,
                                offsets + q_lens,
                                soft_cap=cfg.logits_soft_cap, kcfg=kcfg)
    return _finish(p, out, plan, lay), _pool_back(cache, kc, vc)


def paged_attn_prefill(p, x, cache, offsets, block_tables, cfg, lay: Layout,
                       kcfg=None, layer=None):
    """Chunked prefill against the paged pool — the degenerate mixed call
    with ``q_lens == S`` for every row: all S columns are written (rows
    not in this chunk batch must be all-null so their scatter lands in the
    null block; the zero-padding past a short chunk is causally masked and
    overwritten by the next chunk, exactly as the serialized engine
    expects). x: [B, S_loc, d]; offsets: [B] chunk start positions.
    Returns (out [B, S_loc, d], cache)."""
    S = x.shape[1] * max(lay.sp, 1)            # full chunk width after a2a
    q_lens = jnp.full(offsets.shape, S, jnp.int32)
    return paged_attn_mixed(p, x, cache, offsets, q_lens, block_tables, cfg,
                            lay, kcfg=kcfg, layer=layer)


def paged_attn_decode(p, x, cache, lens, block_tables, cfg, lay: Layout,
                      kcfg=None, layer=None):
    """One-token decode against the paged pool — the C == 1 kernel call.
    x: [B_loc, d]; lens: [B] write positions; block_tables: [B, nmax]
    (all-null rows for inactive slots scatter into the null block);
    ``layer`` as in ``paged_attn_mixed``. Returns (out [B_loc, d], cache)."""
    plan = get_plan(cfg, lay)
    xs = x[None]                                               # batch-as-seq
    q, k, v = _project_exchange(p, xs, cfg, lay, plan)
    B = q.shape[1]
    q = q.transpose(1, 0, 2, 3)                                # [B,1,q_pr,dh]
    k = k.transpose(1, 0, 2, 3)
    v = v.transpose(1, 0, 2, 3)
    pos = lens[:, None]                                        # [B,1]
    q, k = _qk_post(p, q, k, pos, cfg, True)

    kc, vc, base = _pool_view(cache, layer)
    bs = kc.shape[2]
    blk = block_tables[jnp.arange(B), lens // bs] + base       # [B]
    heads = jnp.arange(kc.shape[1])[None, :]
    kc = kc.at[blk[:, None], heads, (lens % bs)[:, None]].set(k[:, 0])
    vc = vc.at[blk[:, None], heads, (lens % bs)[:, None]].set(v[:, 0])
    out = K.paged_ragged_attend(q, kc, vc, block_tables + base,
                                jnp.ones_like(lens), lens + 1,
                                soft_cap=cfg.logits_soft_cap, kcfg=kcfg)
    out = out.transpose(1, 0, 2, 3)                            # [1,B,q_pr,dh]
    out = _finish(p, out, plan, lay)                           # [1,B_loc,d]
    return out[0], _pool_back(cache, kc, vc)


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------
def cross_kv_prefill(p, enc_out, cfg, lay: Layout):
    """Compute the cross-attention KV cache from encoder output (once)."""
    plan = get_plan(cfg, lay)
    dh = cfg.head_dim
    B, S_loc, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, S_loc, -1, dh)
    v = (enc_out @ p["wv"]).reshape(B, S_loc, -1, dh)
    if cfg.qkv_bias:
        k, v = k + p["bk"].reshape(-1, dh), v + p["bv"].reshape(-1, dh)
    if lay.sp > 1:
        k = expand_kv_for_send(k, plan, lay.sp, _tp_rank(lay))
        v = expand_kv_for_send(v, plan, lay.sp, _tp_rank(lay))
        k, v = ulysses_scatter_heads([k, v], lay)
    return {"k": k, "v": v}                                    # [B, S_enc, kv_pr, dh]


def cross_attend(p, x, cross_cache, cfg, lay: Layout, decode: bool = False):
    """Decoder query against (static) cross KV. x: [B, S_loc, d] or [B_loc, d]."""
    plan = get_plan(cfg, lay)
    dh = cfg.head_dim
    xs = x[None] if decode else x
    q = (xs @ p["wq"]).reshape(xs.shape[0], xs.shape[1], -1, dh)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(-1, dh)
    if lay.sp > 1:
        (q,) = ulysses_scatter_heads([q], lay)
    if decode:
        q = q.transpose(1, 0, 2, 3)
    k, v = cross_cache["k"], cross_cache["v"]
    S_enc = k.shape[1]
    qpos = jnp.zeros(q.shape[:2], jnp.int32)
    out = attend(q, k, v, qpos, jnp.arange(S_enc), causal=False)
    if decode:
        out = out.transpose(1, 0, 2, 3)
    out = _finish(p, out, plan, lay)
    return out[0] if decode else out
