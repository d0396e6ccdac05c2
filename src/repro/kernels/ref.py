"""Pure-jnp oracles for every Pallas kernel (the numerics ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention_math import attend as _attend


def flash_attention_ref(q, k, v, *, causal=True):
    """q: [N, Sq, D]; k/v: [Nkv, Skv, D]. Oracle via the model's chunked
    online-softmax attention."""
    N, Sq, D = q.shape
    Nkv, Skv = k.shape[0], k.shape[1]
    qb = q.reshape(1, N, Sq, D).transpose(0, 2, 1, 3)   # [1, Sq, N, D]
    kb = k.reshape(1, Nkv, Skv, D).transpose(0, 2, 1, 3)
    vb = kb * 0 + v.reshape(1, Nkv, Skv, D).transpose(0, 2, 1, 3)
    qpos = jnp.arange(Sq)[None, :]
    kpos = jnp.arange(Skv)
    out = _attend(qb, kb, vb, qpos, kpos, causal=causal)
    return out.transpose(0, 2, 1, 3).reshape(N, Sq, D)


def decode_attention_ref(q, k, v, lens):
    """q: [B, Hkv, g, D]; k/v: [B, Hkv, S, D]; lens: [B]."""
    B, Hkv, g, D = q.shape
    S = k.shape[2]
    qb = q.reshape(B, 1, Hkv * g, D)                    # [B, Sq=1, H, D]
    kb = k.transpose(0, 2, 1, 3)                        # [B, S, Hkv, D]
    vb = v.transpose(0, 2, 1, 3)
    qpos = (lens - 1)[:, None]
    out = _attend(qb, kb, vb, qpos, jnp.arange(S), causal=False, kv_len=lens)
    return out.reshape(B, Hkv, g, D)


def _paged_gather(pool, block_tables):
    """Assemble the logical contiguous view [B, nmax*bs, slots, Dh] of each
    sequence's blocks from a head-minor ``[num_blocks, bs, slots, Dh]`` pool
    (the ragged oracle transposes the head-major pool into this order).
    The block table is in logical order, so gathered kv
    position ``p`` is global position ``p`` (null-block tail entries carry
    garbage and are masked by kv_len).

    This materialized gather is the REFERENCE path only — the model and
    engine stream KV through the block table work-proportionally via the
    ragged Pallas kernel / its jnp mirror. Out-of-bounds table ids clamp
    explicitly (``mode="clip"``) instead of relying on jnp's
    version-dependent OOB-gather default: a clipped read lands on the last
    physical block, which is deterministic garbage already masked by
    ``kv_len`` — never an undefined fill value."""
    B, nmax = block_tables.shape
    bs = pool.shape[1]
    g = jnp.take(pool, block_tables, axis=0,
                 mode="clip")                  # [B, nmax, bs, slots, Dh]
    return g.reshape(B, nmax * bs, pool.shape[2], pool.shape[3])


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lens):
    """Oracle for the paged decode kernel: gather each sequence's blocks in
    logical order into a contiguous [B, Hkv, nmax*bs, D] view, then run the
    contiguous decode oracle. q: [B, Hkv, g, D]; k_pool/v_pool:
    [num_blocks, bs, Hkv, D]; block_tables: [B, nmax]; lens: [B]."""
    k = _paged_gather(k_pool, block_tables).transpose(0, 2, 1, 3)
    v = _paged_gather(v_pool, block_tables).transpose(0, 2, 1, 3)
    return decode_attention_ref(q, k, v, lens)


def paged_ragged_attention_ref(q, k_pool, v_pool, block_tables, q_lens,
                               ctx_lens, *, window=0, soft_cap=0.0):
    """Oracle for the ragged paged kernel. q: [B, Hkv, g, C, D] — C ragged
    query columns per sequence, column c of row b sits at global position
    ``ctx_lens[b] - q_lens[b] + c``; k_pool/v_pool: [num_blocks, Hkv, bs, D]
    (head-major, as the kernel reads them); block_tables: [B, nmax];
    q_lens/ctx_lens: [B]; window/soft_cap as in the kernel. Returns
    [B, Hkv, g, C, D]; columns >= q_lens[b] carry padding positions and are
    don't-care (but match the kernel's masking exactly)."""
    B, Hkv, g, C, D = q.shape
    bs = k_pool.shape[2]
    nmax = block_tables.shape[1]
    kg = _paged_gather(k_pool.transpose(0, 2, 1, 3), block_tables)
    vg = _paged_gather(v_pool.transpose(0, 2, 1, 3), block_tables)
    qb = q.transpose(0, 3, 1, 2, 4).reshape(B, C, Hkv * g, D)
    q_pos = ctx_lens[:, None] - q_lens[:, None] + jnp.arange(C)[None, :]
    out = _attend(qb, kg, vg, q_pos, jnp.arange(nmax * bs), causal=True,
                  window=window, kv_len=ctx_lens, soft_cap=soft_cap)
    # empty rows (ctx == 0): fully-masked softmax degenerates to a mean of
    # the null block; the kernel defines them as zeros instead
    out = jnp.where((ctx_lens > 0)[:, None, None, None], out, 0.0)
    return out.reshape(B, C, Hkv, g, D).transpose(0, 2, 3, 1, 4)


def ssd_chunk_ref(x, b, c, dt, cum):
    """Oracle for the intra-chunk SSD kernel. Shapes as in ssd_chunk_kernel."""
    xf, bf, cf = (t.astype(jnp.float32) for t in (x, b, c))
    dtf = dt[..., 0].astype(jnp.float32)
    cumf = cum[..., 0].astype(jnp.float32)
    L = x.shape[1]
    cb = jnp.einsum("ntd,nsd->nts", cf, bf)
    dec = cumf[:, :, None] - cumf[:, None, :]
    tri = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    sc = cb * jnp.exp(jnp.where(tri[None], dec, -1e30)) * dtf[:, None, :]
    y = jnp.einsum("nts,nsh->nth", sc, xf)
    w = jnp.exp(cumf[:, -1:] - cumf) * dtf
    st = jnp.einsum("nth,ntd->nhd", xf * w[..., None], bf)
    return y, st, jnp.exp(cumf)[..., None]


def rmsnorm_ref(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale
