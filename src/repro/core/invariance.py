"""KV-cache invariance (paper §3.3.1, Fig. 6).

Base config (SP=s, TP=t): after the Ulysses all-to-all, device
(sp_rank=i, tp_rank=j) owns head sub-block ``j*s + i``.  The shift config
(TP=s*t) must shard head dimensions in the *same* order — in JAX, both are
expressed by sharding head dimensions over the axis tuple ``(tp, sp)``
(tp-major).  ``verify_invariance`` proves the property *structurally*: the
byte-range → device map of the cache sharding must be identical under both
configurations, so switching configs shares the cache with zero data
movement.

``verify_paged_invariance`` extends the check to the paged cache
(``repro.cache``): the per-block byte→device map must be config-invariant
AND the block table must be replicated across the model group, so neither
the pool bytes nor the indirection move on an SP↔TP switch.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding


def head_order_base(sp: int, tp: int):
    """Global model-rank (= i*tp + j) that owns each head sub-block in the
    base config. Paper's example (sp=3, tp=2) -> [0, 2, 4, 1, 3, 5]."""
    order = np.empty(sp * tp, dtype=int)
    for i in range(sp):
        for j in range(tp):
            order[j * sp + i] = i * tp + j
    return order.tolist()


def head_order_shift(sp: int, tp: int):
    """Rank order the shift config must traverse to load weight shards so
    that rank g gets the same heads it owns in the base config — the paper's
    SP_TP group (e.g. [[0, 2, 4, 1, 3, 5]])."""
    return head_order_base(sp, tp)


def cache_specs_equal(shape, sharding_a: NamedSharding, sharding_b: NamedSharding) -> bool:
    """Structural equality of two shardings for a given global shape: every
    device must be assigned exactly the same index ranges."""
    ma = sharding_a.devices_indices_map(tuple(shape))
    mb = sharding_b.devices_indices_map(tuple(shape))
    if set(ma) != set(mb):
        return False
    return all(ma[d] == mb[d] for d in ma)


def verify_invariance(cache_tree_shapes, base_specs, shift_specs, mesh) -> bool:
    """Check every leaf of the KV-cache pytree: base vs shift sharding must
    map identical index ranges to identical devices. Works unchanged for the
    head-major paged block pools ([num_blocks, slots, block_size, Dh]):
    only the head slot axis is sharded, so the per-block byte→device map is
    what is compared."""
    shapes = jax.tree.leaves(cache_tree_shapes)
    specs_a = jax.tree.leaves(base_specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    specs_b = jax.tree.leaves(shift_specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(shapes) == len(specs_a) == len(specs_b)
    for sh, pa, pb in zip(shapes, specs_a, specs_b):
        shape = sh.shape if hasattr(sh, "shape") else sh
        a = NamedSharding(mesh, pa)
        b = NamedSharding(mesh, pb)
        if not cache_specs_equal(shape, a, b):
            return False
    return True


def replicated_over_axes(shape, spec, mesh, axes: Sequence[str]) -> bool:
    """True when every device along ``axes`` holds the full array (the other
    mesh axes may shard it)."""
    sh = NamedSharding(mesh, spec)
    m = sh.devices_indices_map(tuple(shape))
    names = list(mesh.axis_names)
    groups = {}
    for idx in np.ndindex(mesh.devices.shape):
        key = tuple(i for n, i in zip(names, idx) if n not in axes)
        groups.setdefault(key, []).append(m[mesh.devices[idx]])
    return all(all(s == g[0] for s in g) for g in groups.values())


def _axis_fraction(sharding_map, shape, axis):
    """Per-device (start, stop) fraction of ``axis`` each device holds."""
    out = {}
    for d, idx in sharding_map.items():
        s = idx[axis]
        lo = 0 if s.start is None else s.start
        hi = shape[axis] if s.stop is None else s.stop
        out[d] = (lo / shape[axis], hi / shape[axis])
    return out


def dp_rows_aligned(pool_shape, pool_spec, table_shape, table_spec,
                    mesh, dp_axes: Sequence[str]) -> bool:
    """Per-dp-row pool/table alignment: the pool's block axis (axis 0, or
    axis 1 under a leading layer-repeat axis — found by rank, like the
    engine's COW body) and the block table's leading slot axis must be
    sharded over the dp axes *identically in fraction* — every device's
    table shard (the slots of the rows it serves) must line up with the
    pool shard holding exactly those rows' physical blocks, or a
    row-local block id would dereference into another row's pool slice
    inside ``shard_map``."""
    if not dp_axes:
        return True
    blk_axis = 1 if len(pool_shape) == 5 else 0
    mp = _axis_fraction(
        NamedSharding(mesh, pool_spec).devices_indices_map(tuple(pool_shape)),
        pool_shape, blk_axis)
    mt = _axis_fraction(
        NamedSharding(mesh, table_spec).devices_indices_map(tuple(table_shape)),
        table_shape, 0)
    return all(mp[d] == mt[d] for d in mt)


def shared_blocks_identical(pool_base, pool_shift,
                            shared_blocks: Sequence[int]) -> bool:
    """Bitwise equality of the listed physical blocks across two pool
    pytrees (e.g. one populated under the base config, one under shift).

    With prefix caching, a multi-ref block may be read by requests admitted
    under EITHER config — its bytes must therefore not depend on which
    config produced them, or an SP↔TP switch would silently change every
    request that shares the block. Pool leaves are ``[num_blocks, slots,
    bs, Dh]`` (or with a leading layer-repeat axis, found by rank)."""
    blocks = np.asarray(list(shared_blocks), np.int32)
    if blocks.size == 0:
        return True
    la = jax.tree.leaves(pool_base)
    lb = jax.tree.leaves(pool_shift)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            return False
        sel = (slice(None), blocks) if a.ndim == 5 else (blocks,)
        if not (a[sel] == b[sel]).all():       # bitwise (no tolerance)
            return False
    return True


def verify_paged_invariance(pool_shapes, base_specs, shift_specs,
                            table_shape, base_table_spec, shift_table_spec,
                            mesh, model_axes: Sequence[str],
                            pool_base=None, pool_shift=None,
                            shared_blocks: Optional[Sequence[int]] = None,
                            dp_axes: Sequence[str] = ()
                            ) -> bool:
    """Paged extension of the §3.3.1 check. Zero-copy SP↔TP switching over a
    paged cache needs BOTH halves:

    1. every physical block pool leaf maps identical byte ranges to
       identical devices under base and shift (the contiguous-cache
       condition, applied per block), and
    2. the block table is replicated across the model group in both
       configs — every rank follows the same logical→physical indirection,
       so the control plane is also untouched by a switch.

    With ``dp_axes`` (per-dp-row pools) a further check runs per row: the
    pool's block axis and the table's slot axis must be dp-sharded in
    lockstep under BOTH configs, so each row's replicated-within-the-group
    table indexes exactly that row's pool slice — per-row invariance, not
    just global.

    When ``pool_base``/``pool_shift`` arrays and a ``shared_blocks`` id list
    are given (prefix caching: blocks with refcount > 1), a third check
    requires those blocks to be *bitwise identical* across the two pools —
    shared prefix blocks are read by sequences under both configs, so their
    contents must not encode which config wrote them. ``shared_blocks``
    are pool-global ids (row offset applied), so the check spans rows."""
    if not verify_invariance(pool_shapes, base_specs, shift_specs, mesh):
        return False
    for spec in (base_table_spec, shift_table_spec):
        if not replicated_over_axes(table_shape, spec, mesh, model_axes):
            return False
    a = NamedSharding(mesh, base_table_spec)
    b = NamedSharding(mesh, shift_table_spec)
    if not cache_specs_equal(table_shape, a, b):
        return False
    if dp_axes:
        shapes = jax.tree.leaves(pool_shapes)
        isp = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
        for specs, tspec in ((jax.tree.leaves(base_specs, is_leaf=isp),
                              base_table_spec),
                             (jax.tree.leaves(shift_specs, is_leaf=isp),
                              shift_table_spec)):
            for sh, ps in zip(shapes, specs):
                shape = sh.shape if hasattr(sh, "shape") else sh
                if not dp_rows_aligned(shape, ps, table_shape, tspec,
                                       mesh, dp_axes):
                    return False
    if shared_blocks is not None:
        assert pool_base is not None and pool_shift is not None, \
            "shared-block check needs both populated pools"
        return shared_blocks_identical(pool_base, pool_shift, shared_blocks)
    return True
