"""Roofline accounting: the loop-aware HLO collective parser against a
compiled program with a known collective schedule, and comm-model sanity."""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from conftest import make_mesh, reduced_cfg
from repro.configs import SHAPES_BY_NAME
from repro.parallel import Layout
from repro.roofline import collective_bytes_hlo, comm_bytes_analytic


def test_hlo_parser_counts_loop_iterations():
    mesh = make_mesh((1, 1, 4))

    def body(x):
        def step(c, _):
            return jax.lax.psum(c, "tp"), None
        out, _ = jax.lax.scan(step, x, None, length=7)
        return out

    f = shard_map(body, mesh=mesh, in_specs=P(None, "tp"),
                  out_specs=P(None, "tp"), check_vma=False)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    total, per, count = collective_bytes_hlo(compiled.as_text())
    # 7 iterations x all-reduce of the local [8, 16] fp32 shard
    expect = 7 * 8 * 16 * 4
    assert count >= 7, count
    assert total >= expect, (total, expect)
    assert total <= 4 * expect, (total, expect)


def test_comm_model_base_vs_shift():
    """Shift config (pure TP) must move more bytes per token than base
    (SP+TP) at large batch — the paper's Table 2 in model form."""
    cfg = reduced_cfg("qwen3-8b")
    mesh = make_mesh((1, 4, 2))
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    shape = SHAPES_BY_NAME["prefill_32k"]
    base = comm_bytes_analytic(cfg, lay, shape, "base")
    shift = comm_bytes_analytic(cfg, lay.to_shift(), shape, "shift")
    assert shift["total"] > base["total"]
