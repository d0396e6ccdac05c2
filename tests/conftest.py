# Multi-device tests (Ulysses/shift equivalence, invariance, ZeRO) need a
# small virtual device pool. 8 devices — NOT the dry-run's 512, which stays
# exclusive to repro.launch.dryrun per the deliverable — keeps single-device
# smoke tests effectively unaffected (they ignore the extra devices).
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import pytest


def make_mesh(shape=(1, 2, 2)):
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(*shape)


@pytest.fixture(scope="session")
def mesh222():
    return make_mesh((2, 2, 2))


@pytest.fixture(scope="session")
def mesh122():
    return make_mesh((1, 2, 2))


def reduced_cfg(name, cap=4.0):
    from repro.configs import get_config
    cfg = get_config(name).reduced()
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=cap))
    return cfg
