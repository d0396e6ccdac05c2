"""Public jit'd wrappers for the Pallas kernels + the paged-attention
dispatch layer.

On CPU (this container) the kernels execute in ``interpret=True`` mode
against the same BlockSpec program; on TPU they compile natively. Padding to
tile boundaries happens here so kernel bodies stay alignment-exact.

``KernelConfig`` keys the paged-attention dispatch the model runs inside
``shard_map``: Pallas on TPU, the bit-exact jnp mirror of the kernel on CPU
(so tier-1 tests and CI exercise the production algorithm on every push),
with ``interpret`` and the legacy materialized-``gather`` oracle available
for parity tests and A/B benchmarks. The backend is resolved once at trace
time — it is a compile-time choice, never a traced value.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel
from .decode_attention import decode_attention_kernel
from .paged_decode_attention import paged_decode_attention_kernel
from .paged_ragged_attention import (paged_ragged_attention_kernel,
                                     paged_ragged_attention_mirror)
from .ssd_scan import ssd_chunk_kernel
from .rmsnorm import rmsnorm_kernel


def _on_cpu():
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# paged-attention dispatch
# ---------------------------------------------------------------------------
ATTN_BACKENDS = ("auto", "pallas", "interpret", "reference", "gather")
# CI sets this to "interpret" so the Pallas program itself (not just its
# mirror) runs under JAX_PLATFORMS=cpu on every push
ATTN_BACKEND_ENV = "REPRO_ATTN_BACKEND"


@dataclass(frozen=True)
class KernelConfig:
    """Which implementation serves the model's paged attention.

    ``auto`` (default): native Pallas on TPU; on every other backend the
    bit-exact jnp mirror of the kernel (``reference``) — same algorithm,
    same op order, bitwise equal to interpret mode on CPU. ``interpret``
    forces interpret-mode Pallas (slow; CI's fallback-exercise mode),
    ``pallas`` forces native compilation, and ``gather`` routes to the
    retained materialized-gather oracle (``kernels.ref``) — the O(B·S_max)
    path the kernel replaced, kept for parity tests and A/B benchmarks.
    """
    attn_backend: str = "auto"

    def __post_init__(self):
        if self.attn_backend not in ATTN_BACKENDS:
            raise ValueError(
                f"attn_backend={self.attn_backend!r} not in {ATTN_BACKENDS}")

    def resolve(self) -> str:
        """Concrete backend for this process (trace-time static). An
        unrecognized ``REPRO_ATTN_BACKEND`` value raises instead of
        silently falling back: CI's interpret-forced leg rides on this
        env var, and a typo that quietly resolved to the mirror would
        green-light a run that never executed the Pallas program."""
        b = self.attn_backend
        if b == "auto":
            b = os.environ.get(ATTN_BACKEND_ENV, "auto")
            if b not in ATTN_BACKENDS:
                raise ValueError(
                    f"{ATTN_BACKEND_ENV}={b!r} not in {ATTN_BACKENDS}")
            if b == "auto":
                b = "pallas" if jax.default_backend() == "tpu" else "reference"
        return b


DEFAULT_KERNEL_CONFIG = KernelConfig()


def paged_ragged_attend(q, k_pool, v_pool, block_tables, q_lens, ctx_lens, *,
                        window=0, soft_cap=0.0, kcfg=None):
    """Work-proportional paged attention over a head-major pool,
    dispatch-keyed.

    q: [B, C, Hq, D] — C ragged query columns (columns >= q_lens[b] are
    padding); k_pool/v_pool: [num_blocks, Hkv, bs, D]; block_tables:
    [B, nmax]; q_lens/ctx_lens: [B] -> [B, C, Hq, D].

    Plain traceable function (no jit of its own): the model calls it inside
    an already-jitted ``shard_map`` body on per-rank shards, where the
    planner guarantees ``Hq % Hkv == 0`` and group alignment."""
    backend = (kcfg or DEFAULT_KERNEL_CONFIG).resolve()
    B, C, Hq, D = q.shape
    Hkv = k_pool.shape[1]
    g = Hq // Hkv
    # With one query row per instance (g*C == 1) XLA:CPU fuses the mirror's
    # contiguous [bs, D] pool slice into a loop-emitted vector dot, while
    # interpret mode dots a separate block buffer: the two sum in different
    # orders. One padding column (masked, discarded) keeps both on the
    # matrix-matrix path, so the CPU backends stay bitwise equal.
    pad = int(g * C == 1 and backend in ("reference", "interpret"))
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C += pad
    qf = q.transpose(0, 2, 1, 3).reshape(B, Hkv, g, C, D)
    if backend == "gather":
        from . import ref
        out = ref.paged_ragged_attention_ref(qf, k_pool, v_pool, block_tables,
                                             q_lens, ctx_lens, window=window,
                                             soft_cap=soft_cap)
    elif backend == "reference":
        out = paged_ragged_attention_mirror(qf, k_pool, v_pool, block_tables,
                                            q_lens, ctx_lens, window=window,
                                            soft_cap=soft_cap)
    else:
        out = paged_ragged_attention_kernel(qf, k_pool, v_pool, block_tables,
                                            q_lens, ctx_lens, window=window,
                                            soft_cap=soft_cap,
                                            interpret=backend == "interpret")
    return out.reshape(B, Hq, C, D).transpose(0, 2, 1, 3)[:, :C - pad]


@partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, bq=128, bk=128):
    """q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D] -> [B, Sq, Hq, D]."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    out = flash_attention_kernel(qf, kf, vf, causal=causal, bq=min(bq, Sq),
                                 bk=min(bk, Skv), interpret=_on_cpu())
    return out.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k, v, lens, *, bk=512):
    """q: [B, 1, Hq, D]; k/v: [B, S, Hkv, D]; lens: [B] -> [B, 1, Hq, D]."""
    B, _, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qf = q.reshape(B, Hkv, g, D)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    out = decode_attention_kernel(qf, kf, vf, lens, bk=min(bk, S),
                                  interpret=_on_cpu())
    return out.reshape(B, 1, Hq, D)


@jax.jit
def paged_decode_attention(q, k_pool, v_pool, block_tables, lens):
    """q: [B, 1, Hq, D]; k_pool/v_pool: [num_blocks, bs, Hkv, D];
    block_tables: [B, nmax]; lens: [B] -> [B, 1, Hq, D]."""
    B, _, Hq, D = q.shape
    Hkv = k_pool.shape[2]
    g = Hq // Hkv
    out = paged_decode_attention_kernel(q.reshape(B, Hkv, g, D), k_pool,
                                        v_pool, block_tables, lens,
                                        interpret=_on_cpu())
    return out.reshape(B, 1, Hq, D)


@partial(jax.jit, static_argnames=("window", "soft_cap", "kcfg"))
def _paged_ragged_attention_jit(q, k_pool, v_pool, block_tables, q_lens,
                                ctx_lens, *, window, soft_cap, kcfg):
    return paged_ragged_attend(q, k_pool, v_pool, block_tables, q_lens,
                               ctx_lens, window=window, soft_cap=soft_cap,
                               kcfg=kcfg)


def paged_ragged_attention(q, k_pool, v_pool, block_tables, q_lens, ctx_lens,
                           *, window=0, soft_cap=0.0, kcfg=None):
    """Jitted entry to ``paged_ragged_attend`` for callers outside the
    model's shard_map (tests, benchmarks). Same contract; work is
    proportional to each sequence's occupied blocks, not nmax.

    The backend is resolved to a CONCRETE KernelConfig before the jit
    boundary so it is part of the cache key — with a lazy ``auto`` the
    first trace would bake the then-current ``REPRO_ATTN_BACKEND`` into
    the cached executable and silently ignore later env changes at the
    same shapes. (The model's step-fn closures resolve at their own trace
    time instead: the env var is a process-startup knob there, set before
    the engine compiles.)"""
    resolved = KernelConfig((kcfg or DEFAULT_KERNEL_CONFIG).resolve())
    return _paged_ragged_attention_jit(q, k_pool, v_pool, block_tables,
                                       q_lens, ctx_lens, window=window,
                                       soft_cap=soft_cap, kcfg=resolved)


@jax.jit
def ssd_chunk(x, b, c, dt, cum):
    return ssd_chunk_kernel(x, b, c, dt, cum, interpret=_on_cpu())


@partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, scale, eps=1e-6):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    n = x2.shape[0]
    br = 256
    pad = (-n) % br if n > br else 0
    if n < br:
        br = n
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_kernel(x2, scale, eps=eps, block_rows=br,
                         interpret=_on_cpu())
    return out[:n].reshape(shape)
