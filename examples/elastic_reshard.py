"""Fault tolerance / elastic scaling demo: re-factorize a live deployment
from (sp=2, tp=2) to (sp=4, tp=2) — e.g. after adding hosts — without a
checkpoint round-trip, and verify outputs are unchanged.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/elastic_reshard.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.ft import reshard_params
from repro.launch.mesh import make_test_mesh
from repro.models.model import Model
from repro.parallel import Layout

cfg = get_config("qwen3-8b").reduced()

mesh_a = make_test_mesh(1, 2, 2)
lay_a = Layout.from_mesh(mesh_a, dp=("data",), sp=("sp",), tp=("tp",))
m_a = Model(cfg=cfg, lay=lay_a, mesh=mesh_a, dtype=jnp.float32)
params = m_a.init_params(jax.random.key(0))

mesh_b = make_test_mesh(1, 4, 2)
lay_b = Layout.from_mesh(mesh_b, dp=("data",), sp=("sp",), tp=("tp",))
m_b = Model(cfg=cfg, lay=lay_b, mesh=mesh_b, dtype=jnp.float32)
params_b = reshard_params(params, m_a, m_b)

toks = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
offs = jnp.zeros((8,), jnp.int32)
la, _ = m_a.prefill_fn()(params, m_a.init_cache(8, 32), toks, offs)
lb, _ = m_b.prefill_fn()(params_b, m_b.init_cache(8, 32), toks, offs)
np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=3e-4, atol=3e-4)
print("elastic reshard (sp=2,tp=2) -> (sp=4,tp=2): outputs identical ✓")
