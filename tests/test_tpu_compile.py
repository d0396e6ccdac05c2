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
def one_chip():
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
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


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
