"""The production paged-attention kernel compiles for a TPU v5e chip.

Interpret mode and the jnp mirror accept block shapes that the TPU's own
compiler (Mosaic) refuses, so the kernel is compiled here, ahead of time,
for a described v5e chip at real model widths. Nothing runs; a pass says
the chip's compiler accepts the program and that the compiled step holds
the kernel as a ``tpu_custom_call`` named ``paged_ragged_attention``.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and each test worker
imports every test file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_ragged_attention import (
    TOKENS_PER_STEP, VMEM_BUDGET, kv_step_blocks, kv_step_vmem_bytes,
    paged_ragged_attention_kernel)


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # entries compiled for a described chip cannot be read back here
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo.devices
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


# B, Hkv, g, C, D, bs, nmax, num_blocks, window, soft_cap
CASES = {
    "qwen3-8b-decode": (8, 8, 4, 1, 128, 16, 256, 2049, 0, 0.0),
    "qwen3-8b-chunk": (8, 8, 4, 64, 128, 16, 256, 2049, 0, 0.0),
    "qwen3-8b-spec-verify": (8, 8, 4, 4, 128, 16, 256, 2049, 0, 0.0),
    "qwen2-1.5b-chunk": (8, 2, 6, 64, 128, 16, 256, 2049, 0, 0.0),
    "window-softcap": (8, 8, 4, 64, 128, 16, 256, 2049, 4096, 50.0),
    "qwen2-1.5b-longdoc-decode": (32, 2, 6, 1, 128, 16, 608, 12921, 0, 0.0),
    "qwen2-1.5b-prefill-256": (8, 2, 6, 256, 128, 16, 608, 12921, 0, 0.0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_paged_ragged_kernel_compiles_for_v5e(one_chip, case):
    B, Hkv, g, C, D, bs, nmax, nb, window, cap = CASES[case]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda *a: paged_ragged_attention_kernel(
        *a, window=window, soft_cap=cap))
    compiled = fn.lower(
        sds((B, Hkv, g, C, D), jnp.bfloat16),
        sds((nb, Hkv, bs, D), jnp.bfloat16),
        sds((nb, Hkv, bs, D), jnp.bfloat16),
        sds((B, nmax), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's name is the custom call's instruction name, which the
    # profiler's XLA Ops line shows (the source path alone would match
    # the bare word)
    assert re.search(r"%paged_ragged_attention(\.\d+)? = \S+ custom-call\(",
                     text)


@pytest.mark.parametrize("case", list(CASES))
def test_kv_step_blocks_fit_the_vmem_budget(case):
    """The kernel's blocks per grid step at each compiled shape: at least
    one, no wider than the table or the aimed-for keys a step, and a step's
    VMEM estimate within the budget."""
    B, Hkv, g, C, D, bs, nmax, nb, window, cap = CASES[case]
    shapes = dict(bs=bs, rows=g * C, head_dim=D, kv_heads=Hkv,
                  dtype=jnp.bfloat16)
    p = kv_step_blocks(nmax=nmax, **shapes)
    assert 1 <= p <= nmax
    assert p * bs <= TOKENS_PER_STEP
    assert kv_step_vmem_bytes(p, **shapes) <= VMEM_BUDGET


@pytest.mark.parametrize("rows,chunk", [(32, 1), (1, 256)])
def test_longdoc_step_keeps_no_pool_sized_temporary(one_chip, rows, chunk):
    """The whole qwen2-1.5b step (28 layers, bf16, the longdoc cell's
    12,921-block pool and 608-block tables) compiled for a v5e holds less
    than one layer's share of the pool in temporaries: the layer scan
    carries the pool and each layer writes it in place, so no pass copies,
    slices or restacks it."""
    from repro.configs import get_config
    from repro.kernels import KernelConfig
    from repro.models.model import Model
    from repro.parallel import Layout
    m = Model(cfg=get_config("qwen2-1.5b"), lay=Layout(), dtype=jnp.bfloat16)
    step = jax.jit(m.forward_fn(paged=True, kernel=KernelConfig("pallas")),
                   donate_argnums=(1,))

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    pool = jax.tree.map(sds, m.abstract_paged_cache(12921, 16))
    i32 = [sds(jax.ShapeDtypeStruct(s, jnp.int32))
           for s in ((rows, chunk), (rows,), (rows,), (rows, 608))]
    compiled = step.lower(jax.tree.map(sds, m.abstract_params()), pool,
                          *i32).compile()
    layer_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(pool)) // m.cfg.num_layers
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("config,rows,chunk", [("base", 16, 256),
                                               ("shift", 16, 1)])
def test_bursty_steps_keep_no_pool_sized_temporary(v5e_2x2, config, rows,
                                                   chunk):
    """The whole qwen3-8b step (36 layers, bf16, the bursty cell's
    2,305-block pool and 144-block tables) compiled for a v5e 2x2 host, in
    base SP2xTP2 (a 16-row prefill launch) and shift TP4 (the 16-row
    decode launch): each device holds less than one layer's share of its
    pool in temporaries."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.configs import get_config
    from repro.kernels import KernelConfig
    from repro.models.model import Model
    from repro.parallel import Layout
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 2, 2), ("data", "sp", "tp"))
    lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
    m = Model(cfg=get_config("qwen3-8b"),
              lay=lay if config == "base" else lay.to_shift(), mesh=mesh,
              dtype=jnp.bfloat16)
    step = jax.jit(m.forward_fn(paged=True, kernel=KernelConfig("pallas")),
                   donate_argnums=(1,))

    def sds(tree, specs):
        return jax.tree.map(lambda a, sp: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, sp)), tree, specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    params = sds(jax.eval_shape(m.init_params, jax.random.key(0)),
                 m.param_specs())
    pool = sds(m.abstract_paged_cache(2305, 16), m.paged_cache_specs())
    rep = NamedSharding(mesh, PartitionSpec())
    i32 = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
           for s in ((rows, chunk), (rows,), (rows,), (rows, 144))]
    compiled = step.lower(params, pool, *i32).compile()
    layer_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        pool)) // len(v5e_2x2) // m.cfg.num_layers
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    assert "tpu_custom_call" in compiled.as_text()
