"""Ulysses fused all-to-all: scatter/gather round trips + send-buffer KV
replication against the expansion law."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import make_mesh
from repro.parallel import Layout, plan_heads
from repro.core.ulysses import (ulysses_scatter_heads, ulysses_gather_heads,
                                expand_kv_for_send)


def test_scatter_is_invariance_reshard():
    """scatter == reshard from P(sp seq, tp heads) to P(-, (tp,sp) heads)."""
    mesh = make_mesh((1, 4, 2))
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    x = jnp.arange(2 * 8 * 8 * 3.0).reshape(2, 8, 8, 3)
    out = shard_map(lambda v: ulysses_scatter_heads([v], lay)[0], mesh=mesh,
                    in_specs=P(None, "sp", "tp", None),
                    out_specs=P(None, None, ("tp", "sp"), None),
                    check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_fused_roundtrip_multi_tensor():
    mesh = make_mesh((1, 4, 2))
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    x = jax.random.normal(jax.random.key(0), (2, 8, 8, 4))
    y = jax.random.normal(jax.random.key(1), (2, 8, 8, 2))

    def f(a, b):
        s = ulysses_scatter_heads([a, b], lay)
        g = ulysses_gather_heads(s, lay)
        return g[0], g[1]

    oa, ob = shard_map(f, mesh=mesh,
                       in_specs=(P(None, "sp", "tp", None),) * 2,
                       out_specs=(P(None, "sp", "tp", None),) * 2,
                       check_vma=False)(x, y)
    np.testing.assert_allclose(np.asarray(oa), np.asarray(x), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ob), np.asarray(y), atol=1e-6)


@pytest.mark.parametrize("hkv,sp,tp", [(2, 4, 2), (1, 4, 2), (2, 2, 2),
                                       (4, 2, 2)])
def test_kv_send_replication(hkv, sp, tp):
    """After expand+scatter, slot u must hold padded kv head
    u*h_kv_pad//slots (the paper's send-buffer replication)."""
    mesh = make_mesh((1, sp, tp))
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    G = sp * tp
    plan = plan_heads(max(8, hkv * 4), hkv, G, tp)
    kexp = max(plan.h_kv_pad, tp)
    # weight-level replicas are equal by construction; model that here
    canon = jnp.arange(2 * 8 * plan.h_kv_pad * 3.0).reshape(2, 8, plan.h_kv_pad, 3)
    kv = jnp.repeat(canon, kexp // plan.h_kv_pad, axis=2)

    def f(v):
        j = jax.lax.axis_index("tp")
        send = expand_kv_for_send(v, plan, lay.sp, j)
        return ulysses_scatter_heads([send], lay)[0]

    out = shard_map(f, mesh=mesh, in_specs=P(None, "sp", "tp", None),
                    out_specs=P(None, None, ("tp", "sp"), None),
                    check_vma=False)(kv)
    out, kvn = np.asarray(out), np.asarray(canon)
    slots = plan.kv_slots_total
    for u in range(slots):
        want = u * plan.h_kv_pad // slots
        np.testing.assert_allclose(out[:, :, u], kvn[:, :, want])
