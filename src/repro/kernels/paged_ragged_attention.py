"""Work-proportional ragged paged GQA attention — the engine's production
attention kernel (Pallas TPU) plus its bit-exact jnp mirror.

Generalizes ``paged_decode_attention.py`` along three axes:

* **Ragged queries** — every sequence brings ``q_lens[b]`` fresh tokens
  (``q_len ∈ {0, 1, …, C}``), so one kernel serves pure decode (``C == 1``),
  chunked prefill, and the engine's mixed prefill+decode batches.  Rows past
  ``q_lens[b]`` are padding; their output is unspecified-but-finite (the
  caller discards them).

* **Work proportional to cache occupancy** — a row's live blocks are
  ``[lo, nblk)``: ``nblk = ceil(ctx_lens[b] / block_size)`` (clipped to the
  table), ``lo`` the first block inside the earliest real query row's
  sliding window (0 without one).  Only live blocks are copied from HBM;
  a grid step with no live block does no compute, no softmax update and
  no copy.  A short sequence in a long-``nmax`` table therefore costs
  ~its own blocks, not ``nmax``.

* **Sliding-window + soft-cap masking** — ``window > 0`` restricts every
  query to its trailing ``window`` keys (blocks entirely below the window
  are never fetched — groundwork for paging the ring-buffer layers), and
  ``soft_cap > 0`` applies the tanh logit cap exactly as
  ``attention_math.attend`` does.

Grid: ``(B, ceil(nmax / P))``.  One grid step owns one sequence's ``P``
consecutive table entries for every local kv head.  The pool is
**head-major**, ``[num_blocks, Hkv, bs, D]``, so one physical block is a
contiguous ``[Hkv, bs, D]`` tile: one DMA per live block brings K (and one
V) for all kv heads into VMEM scratch ``[2, P, Hkv, bs, D]``.  K and V
stay in HBM (``memory_space=pl.ANY``); the block table is
scalar-prefetched.

**Double buffering.** The leading 2 of the scratch is a buffer pair.  While
step ``j`` computes on one buffer, the copies of the next live step are in
flight into the other: step ``j+1`` of the same row, or, at a row's last
live step, the first live step of row ``b+1``.  A live step that nobody
prefetched (the kernel's first, or one after an empty row) starts its own
copies.  The buffer index lives in SMEM scratch and flips once per live
step.  Both grid axes are sequential (``arbitrary``).

Blocks of a live step that are not live (past the occupancy, below the
window, or past the table) are not copied, so their buffer slots hold
stale data: their scores are masked to ``NEG_INF`` and their V rows are
zeroed before the ``p @ v`` product, so stale bytes never reach a result.

GQA runs by **group broadcast**: each kv head's query group is ``[g*C, D]``
rows of online-softmax state against the step's ``[P*bs, D]`` keys — the
KV is never expanded to the query head count, neither in HBM nor in VMEM.

``P`` (``kv_step_blocks``) is derived from the static shapes: about
``TOKENS_PER_STEP`` keys a step, halved until the estimated VMEM of the
step (f32 score tile ``[Hkv*g*C, P*bs]``, the two KV buffer pairs, the
online-softmax state and the pipelined q/out blocks) fits
``VMEM_BUDGET``, and never more than the table width.  Wide prefill
chunks get a smaller ``P``; decode gets the full one.

The output for ragged column ``c`` attends positions
``0 .. ctx_lens[b]-q_lens[b]+c`` (causal over the global positions of the
ragged tail).

``paged_ragged_attention_mirror`` is the CPU reference oracle: the SAME
algorithm — identical step loop, identical op sequence, identical skip
conditions expressed as conds — in pure jnp.  On CPU it is *bitwise
identical* to ``interpret=True`` execution of the kernel
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
TOKENS_PER_STEP = 512           # keys a grid step aims for
VMEM_BUDGET = 12 * 2 ** 20      # bytes a step's buffers and tiles may take
_LANES = 128                    # a [rows, 1] f32 VMEM array fills whole lanes


def kv_step_vmem_bytes(p, *, bs, rows, head_dim, kv_heads, dtype):
    """Estimated VMEM of one grid step holding ``p`` blocks: the f32 score
    tile over every kv head, the double-buffered K and V blocks, the
    online-softmax state (``acc`` plus lane-padded ``m``/``l``) and the
    double-buffered q and output blocks. ``rows`` is ``g*C``."""
    item = jnp.dtype(dtype).itemsize
    q_rows = kv_heads * rows
    score = q_rows * p * bs * 4
    kv = 2 * 2 * p * kv_heads * bs * head_dim * item
    state = q_rows * (head_dim + 2 * _LANES) * 4
    q_out = 2 * 2 * q_rows * head_dim * item
    return score + kv + state + q_out


def kv_step_blocks(*, bs, nmax, rows, head_dim, kv_heads, dtype) -> int:
    """``P``, the KV blocks one grid step handles: ``TOKENS_PER_STEP // bs``
    capped at the table width ``nmax``, halved while the step's VMEM
    estimate exceeds ``VMEM_BUDGET`` (at least 1)."""
    p = max(1, min(TOKENS_PER_STEP // bs, nmax))
    while p > 1 and kv_step_vmem_bytes(
            p, bs=bs, rows=rows, head_dim=head_dim, kv_heads=kv_heads,
            dtype=dtype) > VMEM_BUDGET:
        p //= 2
    return p


def _window_lo_block(ctx, q_len, bs, window):
    """First block holding an in-window key for the earliest real query row
    (global position ``ctx - q_len``). Blocks below it are fully masked for
    every real row: masked blocks seen before a row's first live key zero
    out through the online-softmax correction factor (``NEG_INF`` is a
    finite float, so ``exp(m_prev - m_new) == 0`` once a live key lands),
    so skipping them is exact, not approximate."""
    return jnp.maximum(ctx - q_len - window + 1, 0) // bs


def _row_span(ctx, q_len, *, bs, nmax, window):
    """A row's live blocks ``[lo, nblk)`` and whether it has any. ``nblk``
    is at most the table width: a degenerate-prefill ctx may overhang the
    table (s_max % chunk != 0 padding), and positions past it are absent."""
    nblk = jnp.clip(pl.cdiv(ctx, bs), 1, nmax)
    lo = _window_lo_block(ctx, q_len, bs, window) if window else 0
    return lo, nblk, (ctx > 0) & (lo < nblk)


def _masks(j, ctx, q_len, lo, nblk, *, rows, C, P, bs, D, window):
    """Score mask ``[rows, P*bs]`` and key-row mask ``[P*bs, D]`` of grid
    step ``j``. A key is live when its block is in ``[lo, nblk)``; the
    score mask adds causality, the context length and the window."""
    shape = (rows, P * bs)
    # row r of the flattened [g*C] axis is ragged column c = r % C whose
    # global query position is ctx - q_len + c
    qpos = ctx - q_len + jax.lax.broadcasted_iota(jnp.int32, shape, 0) % C
    kpos = j * (P * bs) + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    msk = (kpos >= lo * bs) & (kpos < nblk * bs) & (kpos <= qpos) & (kpos < ctx)
    if window:
        msk &= kpos > qpos - window
    vpos = j * (P * bs) + jax.lax.broadcasted_iota(jnp.int32, (P * bs, D), 0)
    return msk, (vpos >= lo * bs) & (vpos < nblk * bs)


def _attend_step(q, k, v, msk, vok, m, l, acc, *, scale, soft_cap):
    """One kv head's online-softmax update against a step's keys.
    q: [rows, D]; k/v: [P*bs, D]; m/l: [rows, 1]; acc: [rows, D]."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if soft_cap:
        s = soft_cap * jnp.tanh(s / soft_cap)
    s = jnp.where(msk, s, NEG_INF)
    # unfetched key rows hold stale bytes: zero them so p == 0 meets 0
    v = jnp.where(vok, v, jnp.zeros_like(v))
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _kernel(bt_ref, qlen_ref, ctx_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            P, bs, C, scale, window, soft_cap):
    b, j = pl.program_id(0), pl.program_id(1)
    B, nmax = bt_ref.shape
    nsteps = pl.num_programs(1)
    hkv, D = k_buf.shape[2], k_buf.shape[-1]
    rows = q_ref.shape[2] * C
    span = functools.partial(_row_span, bs=bs, nmax=nmax, window=window)

    def copies(r, step, slot):
        """K and V copies of grid step (r, step)'s live blocks into buffer
        ``slot``, each with its liveness predicate."""
        lo, nblk, _ = span(ctx_ref[r], qlen_ref[r])
        out = []
        for p in range(P):
            ib = step * P + p
            blk = bt_ref[r, jnp.minimum(ib, nmax - 1)]
            out.append(((ib >= lo) & (ib < nblk),
                        pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot, p],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot, p],
                                              sems.at[1, slot])))
        return out

    def start(r, step, slot):
        for ok, ck, cv in copies(r, step, slot):
            @pl.when(ok)
            def _():
                ck.start()
                cv.start()

    def wait(r, step, slot):
        for ok, ck, cv in copies(r, step, slot):
            @pl.when(ok)
            def _():
                ck.wait()
                cv.wait()

    ctx, q_len = ctx_ref[b], qlen_ref[b]
    lo, nblk, work = span(ctx, q_len)
    first, last = lo // P, (nblk - 1) // P

    @pl.when((b == 0) & (j == 0))
    def _slot0():
        slot_ref[0] = 0

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(work & (j >= first) & (j <= last))
    def _compute():
        slot = slot_ref[0]
        prev = jnp.maximum(b - 1, 0)
        prefetched = (j > first) | (
            (b > 0) & span(ctx_ref[prev], qlen_ref[prev])[2])

        @pl.when(~prefetched)
        def _():
            start(b, j, slot)

        @pl.when(j < last)
        def _():
            start(b, j + 1, 1 - slot)

        nxt = jnp.minimum(b + 1, B - 1)
        n_lo, _, n_work = span(ctx_ref[nxt], qlen_ref[nxt])

        @pl.when((j == last) & (b + 1 < B) & n_work)
        def _():
            start(nxt, n_lo // P, 1 - slot)

        wait(b, j, slot)
        msk, vok = _masks(j, ctx, q_len, lo, nblk, rows=rows, C=C, P=P,
                          bs=bs, D=D, window=window)
        for h in range(hkv):
            m, l, acc = _attend_step(
                q_ref[0, h].reshape(rows, D),
                k_buf[slot, :, h].reshape(P * bs, D),
                v_buf[slot, :, h].reshape(P * bs, D),
                msk, vok, m_ref[h], l_ref[h], acc_ref[h], scale=scale,
                soft_cap=soft_cap)
            m_ref[h] = m
            l_ref[h] = l
            acc_ref[h] = acc
        slot_ref[0] = 1 - slot

    @pl.when(j == nsteps - 1)
    def _done():
        g = o_ref.shape[2]
        for h in range(hkv):
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).reshape(
                g, C, D).astype(o_ref.dtype)


def _step_blocks(q, k_pool, nmax):
    _, Hkv, g, C, D = q.shape
    return kv_step_blocks(bs=k_pool.shape[2], nmax=nmax, rows=g * C,
                          head_dim=D, kv_heads=Hkv, dtype=k_pool.dtype)


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
    P = _step_blocks(q, k_pool, nmax)
    kern = functools.partial(_kernel, P=P, bs=bs, C=C, scale=D ** -0.5,
                             window=window, soft_cap=soft_cap)
    row = pl.BlockSpec((1, Hkv, g, C, D), lambda b, j, *_: (b, 0, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # block_tables, q_lens, ctx_lens
        grid=(B, pl.cdiv(nmax, P)),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, P, Hkv, bs, D), k_pool.dtype),
            pltpu.VMEM((2, P, Hkv, bs, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),    # [K/V, buffer]
            pltpu.SMEM((1,), jnp.int32),        # buffer of the next live step
            pltpu.VMEM((Hkv, g * C, 1), jnp.float32),
            pltpu.VMEM((Hkv, g * C, 1), jnp.float32),
            pltpu.VMEM((Hkv, g * C, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, C, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_ragged_attention",
    )(block_tables.astype(jnp.int32), q_lens.astype(jnp.int32),
      ctx_lens.astype(jnp.int32), q, k_pool, v_pool)


def paged_ragged_attention_mirror(q, k_pool, v_pool, block_tables, q_lens,
                                  ctx_lens, *, window=0, soft_cap=0.0):
    """Pure-jnp mirror of ``_kernel`` — the dispatch layer's CPU reference
    oracle. Same shapes/contract as ``paged_ragged_attention_kernel``.

    Executes ONE sequential ``fori_loop`` over the flattened
    ``(B, ceil(nmax/P))`` grid in interpret mode's iteration order (``j``
    innermost), with unbatched per-head ops and every ``pl.when`` of the
    kernel expressed as a ``lax.cond`` — the exact structure interpret
    mode stages (``discharge_state`` turns its predicated blocks into
    conds too). BOTH structural choices are load-bearing for the bitwise
    contract: batching the instances with ``vmap`` turns the per-step
    dots into batched dots, and replacing the conds with ``where``-selects
    lets XLA fuse the (discarded) compute into a different context — each
    perturbs tiny-shape reductions by an ulp. Sequenced and
    cond-predicated, the outputs are BITWISE equal to interpret-mode
    execution on CPU. The DMA and its double buffer carry no values of
    their own: a step's blocks are sliced straight from the pool (dead
    ones from the null block, whose rows the V mask zeroes as the kernel
    zeroes its stale buffer rows). Work-proportionality here is
    algorithmic, not wall-clock; the real copy/compute skip only exists
    on the Pallas side."""
    B, Hkv, g, C, D = q.shape
    bs = k_pool.shape[2]
    nmax = block_tables.shape[1]
    P = _step_blocks(q, k_pool, nmax)
    nsteps = pl.cdiv(nmax, P)
    rows = g * C
    scale = D ** -0.5
    block_tables = block_tables.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    ctx_lens = ctx_lens.astype(jnp.int32)
    span = functools.partial(_row_span, bs=bs, nmax=nmax, window=window)

    out0 = jnp.zeros((B, Hkv, g, C, D), q.dtype)
    m0 = jnp.full((Hkv, rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, rows, 1), jnp.float32)
    a0 = jnp.zeros((Hkv, rows, D), jnp.float32)

    def body(i, st):
        out, m, l, acc = st
        b, j = i // nsteps, i % nsteps
        ctx, q_len = ctx_lens[b], q_lens[b]
        lo, nblk, work = span(ctx, q_len)

        m, l, acc = jax.lax.cond(                    # _init
            j == 0,
            lambda m, l, a: (jnp.full_like(m, NEG_INF), jnp.zeros_like(l),
                             jnp.zeros_like(a)),
            lambda m, l, a: (m, l, a), m, l, acc)

        def compute(m, l, acc):                      # _compute
            ib = j * P + jnp.arange(P)
            blk = jnp.where((ib >= lo) & (ib < nblk),
                            block_tables[b, jnp.minimum(ib, nmax - 1)], 0)
            kb = jnp.stack([jax.lax.dynamic_index_in_dim(k_pool, blk[p], 0,
                                                         keepdims=False)
                            for p in range(P)])      # [P, Hkv, bs, D]
            vb = jnp.stack([jax.lax.dynamic_index_in_dim(v_pool, blk[p], 0,
                                                         keepdims=False)
                            for p in range(P)])
            msk, vok = _masks(j, ctx, q_len, lo, nblk, rows=rows, C=C, P=P,
                              bs=bs, D=D, window=window)
            ms, ls, accs = [], [], []
            for h in range(Hkv):
                mh, lh, ah = _attend_step(
                    q[b, h].reshape(rows, D), kb[:, h].reshape(P * bs, D),
                    vb[:, h].reshape(P * bs, D), msk, vok, m[h], l[h],
                    acc[h], scale=scale, soft_cap=soft_cap)
                ms.append(mh)
                ls.append(lh)
                accs.append(ah)
            return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)

        live = work & (j >= lo // P) & (j <= (nblk - 1) // P)
        m, l, acc = jax.lax.cond(live, compute,
                                 lambda m, l, a: (m, l, a), m, l, acc)

        def write(out):                              # _done
            o = (acc / jnp.maximum(l, 1e-30)).reshape(Hkv, g, C, D)
            return jax.lax.dynamic_update_index_in_dim(
                out, o.astype(q.dtype), b, 0)

        out = jax.lax.cond(j == nsteps - 1, write, lambda o: o, out)
        return (out, m, l, acc)

    out, _, _, _ = jax.lax.fori_loop(0, B * nsteps, body, (out0, m0, l0, a0))
    return out
