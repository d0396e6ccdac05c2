"""Work-proportional ragged paged GQA attention — the engine's production
attention kernel (Pallas TPU) plus its bit-exact jnp mirror.

Generalizes ``paged_decode_attention.py`` along three axes:

* **Ragged queries** — every sequence brings ``q_lens[b]`` fresh tokens
  (``q_len ∈ {0, 1, …, C}``), so one kernel serves pure decode (``C == 1``),
  chunked prefill, and the engine's mixed prefill+decode batches.  Rows past
  ``q_lens[b]`` are padding; their output is unspecified-but-finite (the
  caller discards them).

* **Work proportional to cache occupancy** — the per-sequence block count
  ``ceil(ctx_lens[b] / block_size)`` is derived from the scalar-prefetched
  ``ctx_lens`` and every grid step past it is ``pl.when``-skipped entirely
  (no compute, no softmax update, no output write).  The index map routes
  skipped steps to the null block (0), so the pipeline never re-DMAs a
  block for them.  A short sequence in a long-``nmax`` table therefore
  costs ~its own blocks, not ``nmax``.

* **Sliding-window + soft-cap masking** — ``window > 0`` restricts every
  query to its trailing ``window`` keys (blocks entirely below the
  earliest real query row's window are skipped AND null-routed like the
  occupancy tail — groundwork for paging the ring-buffer layers), and
  ``soft_cap > 0`` applies the tanh logit cap exactly as
  ``attention_math.attend`` does.

GQA runs by **group broadcast**: one grid instance owns a kv head's whole
query group as ``[g*C, D]`` rows of online-softmax state against the
``[bs, D]`` kv block — the KV is never expanded to the query head count,
neither in HBM nor in VMEM.

The pool is **head-major**, ``[num_blocks, Hkv, bs, D]``: one kv head's
block is a contiguous ``[bs, D]`` tile, so the KV BlockSpec is
``(None, None, bs, D)`` and its last two dims are the array's own — the
shape Mosaic requires on TPU. (A head-minor ``[num_blocks, bs, Hkv, D]``
pool would need a size-1 block on the second-minor Hkv axis, which the
TPU compiler refuses.)

Grid: ``(B*Hkv, nmax)``.  The output for ragged column ``c`` attends
positions ``0 .. ctx_lens[b]-q_lens[b]+c`` (causal over the global
positions of the ragged tail).

``paged_ragged_attention_mirror`` is the CPU reference oracle: the SAME
algorithm — identical block loop, identical op sequence, identical skip
conditions expressed as state selects — in pure jnp.  On CPU it is
*bitwise identical* to ``interpret=True`` execution of the kernel
(``tests/test_workprop_attention.py`` enforces this), which is what lets
tier-1 CI exercise the production code path without a TPU.  When editing
one, edit the other in lockstep or the bitwise contract breaks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _window_lo_block(ctx, q_len, bs, window):
    """First block holding an in-window key for the earliest real query row
    (global position ``ctx - q_len``). Blocks below it are fully masked for
    every real row: masked blocks seen before a row's first live key zero
    out through the online-softmax correction factor (``NEG_INF`` is a
    finite float, so ``exp(m_prev - m_new) == 0`` once a live key lands),
    so skipping them is exact, not approximate."""
    return jnp.maximum(ctx - q_len - window + 1, 0) // bs


def _kernel(bt_ref, qlen_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs, hkv, C, scale, window, soft_cap):
    n = pl.program_id(0)
    ib = pl.program_id(1)
    b = n // hkv
    ctx = ctx_ref[b]
    # blocks this sequence actually occupies: at least 1 so the ib == 0 step
    # still initializes + writes (empty rows produce zeros, not garbage),
    # and at most the grid — a degenerate-prefill ctx may overhang the
    # table (s_max % chunk != 0 padding), and an unclamped nblk would put
    # the output write past the last grid step (never executed)
    nblk = jnp.clip(pl.cdiv(ctx, bs), 1, pl.num_programs(1))
    live = (ib < nblk) & (ctx > 0)
    if window:
        live &= ib >= _window_lo_block(ctx, qlen_ref[b], bs, window)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].reshape(-1, q_ref.shape[-1])    # [g*C, D]
        k = k_ref[...]                                  # [bs, D]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if soft_cap:
            s = soft_cap * jnp.tanh(s / soft_cap)
        # row r of the flattened [g*C] axis is ragged column c = r % C whose
        # global query position is ctx - q_len + c
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % C
        qpos = ctx - qlen_ref[b] + c
        kpos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        msk = (kpos <= qpos) & (kpos < ctx)
        if window:
            msk &= kpos > qpos - window
        s = jnp.where(msk, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ib == nblk - 1)
    def _done():
        g = o_ref.shape[2]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).reshape(g, C, o_ref.shape[-1]).astype(o_ref.dtype)


def paged_ragged_attention_kernel(q, k_pool, v_pool, block_tables, q_lens,
                                  ctx_lens, *, window=0, soft_cap=0.0,
                                  interpret=False):
    """q: [B, Hkv, g, C, D] — C ragged query columns per sequence;
    k_pool/v_pool: [num_blocks, Hkv, bs, D] (head-major);
    block_tables: [B, nmax] (logical→physical, 0 = null block);
    q_lens: [B] fresh tokens this call (columns >= q_lens[b] are padding);
    ctx_lens: [B] total valid kv length incl. the fresh tokens — MAY exceed
    ``nmax*bs`` when a degenerate-prefill chunk's padding overhangs the
    table (s_max % chunk != 0): positions past the table are simply absent,
    exactly as the gather oracle's kv_len mask over its nmax*bs view;
    window: sliding-window size (0 = full causal); soft_cap: tanh logit cap
    (0 = off). Returns [B, Hkv, g, C, D]; padding columns are unspecified."""
    B, Hkv, g, C, D = q.shape
    bs = k_pool.shape[2]
    nmax = block_tables.shape[1]
    kern = functools.partial(_kernel, bs=bs, hkv=Hkv, C=C, scale=D ** -0.5,
                             window=window, soft_cap=soft_cap)

    def kv_map(n, ib, bt, ql, cl):
        # route every skipped step (past the occupancy, or fully below the
        # sliding window) to the null block: the pipeline re-DMAs nothing
        # for it, and a stale table tail can't be touched either
        b = n // Hkv
        ctx = cl[b]
        live = ib < jnp.clip(pl.cdiv(ctx, bs), 1, nmax)
        if window:
            live &= ib >= _window_lo_block(ctx, ql[b], bs, window)
        return (jnp.where(live, bt[b, ib], 0), n % Hkv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # block_tables, q_lens, ctx_lens
        grid=(B * Hkv, nmax),
        in_specs=[
            pl.BlockSpec((1, 1, g, C, D),
                         lambda n, ib, bt, ql, cl: (n // Hkv, n % Hkv,
                                                    0, 0, 0)),
            pl.BlockSpec((None, None, bs, D), kv_map),
            pl.BlockSpec((None, None, bs, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, C, D),
                               lambda n, ib, bt, ql, cl: (n // Hkv, n % Hkv,
                                                          0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * C, 1), jnp.float32),
            pltpu.VMEM((g * C, 1), jnp.float32),
            pltpu.VMEM((g * C, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, C, D), q.dtype),
        interpret=interpret,
        name="paged_ragged_attention",
    )(block_tables.astype(jnp.int32), q_lens.astype(jnp.int32),
      ctx_lens.astype(jnp.int32), q, k_pool, v_pool)
    return out


def paged_ragged_attention_mirror(q, k_pool, v_pool, block_tables, q_lens,
                                  ctx_lens, *, window=0, soft_cap=0.0):
    """Pure-jnp mirror of ``_kernel`` — the dispatch layer's CPU reference
    oracle. Same shapes/contract as ``paged_ragged_attention_kernel``.

    Executes ONE sequential ``fori_loop`` over the flattened
    ``(B*Hkv, nmax)`` grid in interpret mode's iteration order (``ib``
    innermost), with unbatched per-step ops and every ``pl.when`` of the
    kernel expressed as a ``lax.cond`` — the exact structure interpret
    mode stages (``discharge_state`` turns its predicated blocks into
    conds too). BOTH structural choices are load-bearing for the bitwise
    contract: batching the instances with ``vmap`` turns the per-step
    dots into batched dots, and replacing the conds with ``where``-selects
    lets XLA fuse the (discarded) compute into a different context — each
    perturbs tiny-shape reductions by an ulp. Sequenced and
    cond-predicated, the outputs are BITWISE equal to interpret-mode
    execution on CPU, at ~a tenth of its wall time (none of the
    interpreter's block-copy machinery). Work-proportionality here is
    algorithmic, not wall-clock; the real DMA/compute skip only exists on
    the Pallas side."""
    B, Hkv, g, C, D = q.shape
    bs = k_pool.shape[2]
    nmax = block_tables.shape[1]
    scale = D ** -0.5
    block_tables = block_tables.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    ctx_lens = ctx_lens.astype(jnp.int32)
    qf = q.reshape(B * Hkv, g * C, D)

    out0 = jnp.zeros((B * Hkv, g, C, D), q.dtype)
    m0 = jnp.full((g * C, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g * C, 1), jnp.float32)
    a0 = jnp.zeros((g * C, D), jnp.float32)

    def body(i, st):
        out, m, l, acc = st
        n, ib = i // nmax, i % nmax
        b, h = n // Hkv, n % Hkv
        ctx = ctx_lens[b]
        q_len = q_lens[b]
        nblk = jnp.clip(pl.cdiv(ctx, bs), 1, nmax)   # lockstep with _kernel
        live = (ib < nblk) & (ctx > 0)
        if window:
            live &= ib >= _window_lo_block(ctx, q_len, bs, window)

        m, l, acc = jax.lax.cond(                    # _init
            ib == 0,
            lambda m, l, a: (jnp.full_like(m, NEG_INF), jnp.zeros_like(l),
                             jnp.zeros_like(a)),
            lambda m, l, a: (m, l, a), m, l, acc)

        def compute(m, l, acc):                      # _compute
            qm = jax.lax.dynamic_index_in_dim(qf, n, 0, keepdims=False)
            blk = block_tables[b, ib]
            k = jax.lax.dynamic_slice(k_pool, (blk, h, 0, 0),
                                      (1, 1, bs, D)).reshape(bs, D)
            v = jax.lax.dynamic_slice(v_pool, (blk, h, 0, 0),
                                      (1, 1, bs, D)).reshape(bs, D)
            s = jax.lax.dot_general(qm, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if soft_cap:
                s = soft_cap * jnp.tanh(s / soft_cap)
            c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % C
            qpos = ctx - q_len + c
            kpos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            msk = (kpos <= qpos) & (kpos < ctx)
            if window:
                msk &= kpos > qpos - window
            s = jnp.where(msk, s, NEG_INF)

            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1, keepdims=True)
            acc_new = acc * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.cond(live, compute,
                                 lambda m, l, a: (m, l, a), m, l, acc)

        def write(out):                              # _done
            o = (acc / jnp.maximum(l, 1e-30)).reshape(g, C, D).astype(q.dtype)
            return jax.lax.dynamic_update_index_in_dim(out, o, n, 0)

        out = jax.lax.cond(ib == nblk - 1, write, lambda o: o, out)
        return (out, m, l, acc)

    out, _, _, _ = jax.lax.fori_loop(0, B * Hkv * nmax, body,
                                     (out0, m0, l0, a0))
    return out.reshape(B, Hkv, g, C, D)
