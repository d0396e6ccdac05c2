"""The engine's swappable execution state.

A :class:`Deployment` owns everything that depends on the parallel layout:
the two :class:`~repro.models.model.Model` views (base = SP,TP and shift =
pure TP over the same weights), their sharded parameter trees, and the
jitted step-fn tables compiled against the layout's mesh. ``ShiftEngine``
holds exactly one Deployment and delegates ``base/shift/p_base/p_shift/
dp/_forward/_prefill/_decode`` to it; ``ShiftEngine.reshard(new_layout)``
swaps the whole value between iterations — weights move through the proven
``ft/elastic.reshard_params`` round-trip, the paged pool's committed
blocks re-pour into the new dp-row layout as a typed block-granular plan,
and step-fns recompile lazily on first use of each shape.

Layout is therefore a *value* of the engine, not a constructor constant:
the compat checks live in ``repro.parallel.layout_delta`` and the reshard
protocol (validate -> plan -> mutate) in ``ShiftEngine.reshard``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Set, Tuple

import jax

from repro.models.model import Model
from repro.parallel import Layout, LayoutDelta


def _jit_step(fn, name: str):
    """Jit a step program (the pool, argument 1, donated) under a stable
    name: the profiler's ``XLA Modules`` line then reads ``jit_<name>``
    (``jit_step_base``, ``jit_decode_shift``, ...), so base and shift,
    mixed and serialized launches are told apart in a trace."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, donate_argnums=(1,))


def _recorded(fn, built: set):
    """``fn``, adding the ``(rows, chunk)`` shape of each launch that has
    run to ``built``: a shape that has run once is compiled."""
    @functools.wraps(fn)
    def call(params, cache, toks, *rest):
        out = fn(params, cache, toks, *rest)
        built.add(tuple(toks.shape))
        return out
    return call


class ReshardError(RuntimeError):
    """A reshard request that cannot be satisfied. Raised BEFORE any
    engine state is mutated — the engine keeps serving on its current
    deployment when this propagates."""


@dataclass(frozen=True)
class ReshardReport:
    """What a completed ``ShiftEngine.reshard`` did, as data.

    ``plan`` is the typed block-granular move list — one tuple of
    :class:`~repro.cluster.migration.TransferOp` per live request that was
    re-poured into the new pool layout (PR 8's extract→admit→copy→release
    shape, replica-local)."""

    delta: LayoutDelta
    moved_requests: int
    blocks_moved: int
    dropped_prefix_blocks: int
    plan: Tuple[tuple, ...] = ()
    # steps ``schedule_reshard`` held admissions before executing the swap
    # (0 for an immediate ``reshard()`` call): fewer live blocks to re-pour
    admission_paused_steps: int = 0

    @property
    def noop(self) -> bool:
        return self.delta.kind == "same"


@dataclass
class Deployment:
    """Layout-dependent execution state, swappable as one value.

    ``forward`` is the mixed-batch jit table ({config -> jitted fn}) and is
    ``None`` when the engine runs the serialized iteration, in which case
    ``prefill``/``decode`` carry the 2×2 table instead. ``built`` holds the
    ``(rows, chunk)`` shapes either config's ``forward`` program has run,
    for the engine or for a warm-up calling it directly."""

    base: Model
    shift: Model
    p_base: object
    p_shift: object
    mixed: bool
    paged: bool
    kernel: Optional[object] = None
    forward: Optional[dict] = None
    prefill: Optional[dict] = None
    decode: Optional[dict] = None
    # lazily-populated speculative verify table: {(config, n_last) -> fn}.
    # Rebuilt empty on reshard (layouts change the program); n_last == 1
    # aliases the plain ``forward`` table so a no-draft step runs the
    # exact pre-spec compiled program.
    spec_forward: Optional[dict] = None
    built: Set[Tuple[int, int]] = field(default_factory=set)

    # ------------------------------------------------------------ identity
    @property
    def mesh(self):
        return self.base.mesh

    @property
    def layout(self) -> Layout:
        return self.base.lay

    @property
    def dp(self) -> int:
        return max(self.base.lay.dp, 1)

    @property
    def signature(self):
        return self.base.lay.signature

    # ------------------------------------------------------------ factory
    @classmethod
    def build(cls, model_base: Model, model_shift: Model,
              params_base, params_shift, *, mixed: bool, paged: bool,
              kernel=None) -> "Deployment":
        d = cls(base=model_base, shift=model_shift,
                p_base=params_base, p_shift=params_shift,
                mixed=mixed, paged=paged, kernel=kernel)
        d._compile()
        return d

    def _compile(self):
        kc = self.kernel
        models = {"base": self.base, "shift": self.shift}
        if self.mixed:
            # ONE unified program per config replaces the 2×2
            # prefill/decode table: prefill chunks and decode rows share a
            # forward pass, so the policy prices the real iteration.
            self.forward = {
                c: _recorded(_jit_step(m.forward_fn(paged=True, kernel=kc),
                                       f"step_{c}"), self.built)
                for c, m in models.items()}
            self.spec_forward = {}
        else:
            pg = self.paged
            self.prefill = {
                c: _jit_step(m.prefill_fn(paged=pg, kernel=kc),
                             f"prefill_{c}") for c, m in models.items()}
            self.decode = {
                c: _jit_step(m.decode_fn(True, paged=pg, kernel=kc),
                             f"decode_{c}") for c, m in models.items()}

    # -------------------------------------------------------- spec verify
    def forward_at(self, config: str, n_last: int = 1):
        """The mixed forward for ``config`` ("base" | "shift") at
        speculative verify width ``n_last``. Width 1 returns the plain
        ``forward`` entry unchanged (bitwise the non-spec program);
        wider programs jit once per (config, n_last) and are retired
        with the Deployment on reshard."""
        if self.forward is None:
            raise ValueError("forward_at requires the mixed jit table")
        if n_last <= 1:
            return self.forward[config]
        key = (config, n_last)
        fn = self.spec_forward.get(key)
        if fn is None:
            model = self.base if config == "base" else self.shift
            fn = _jit_step(model.forward_fn(paged=True, kernel=self.kernel,
                                            n_last=n_last),
                           f"step_{config}_n{n_last}")
            self.spec_forward[key] = fn
        return fn

    # ------------------------------------------------------------ reshard
    def reshard(self, new_base: Model, new_shift: Model) -> "Deployment":
        """A fresh Deployment over the new models' layout. Weights move
        through ``ft/elastic.reshard_params`` (bitwise for same-shape
        leaves; replication-expanded leaves re-derive from init); jit
        tables are rebuilt and compile lazily per shape."""
        from repro.ft.elastic import reshard_params
        return Deployment.build(
            new_base, new_shift,
            reshard_params(self.p_base, self.base, new_base),
            reshard_params(self.p_shift, self.shift, new_shift),
            mixed=self.mixed, paged=self.paged, kernel=self.kernel)
