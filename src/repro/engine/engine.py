"""Shift-Parallelism serving engine.

One deployment holds TWO compiled program sets over the SAME weights and ONE
KV cache (paper §3.3): the *base* config (SP,TP — TTFT/throughput-optimal)
and the *shift* config (pure TP — TPOT-optimal). Each iteration the
controller counts batched tokens and picks the config (Algorithm 2); the
cache shardings are structurally identical, so switching configs moves zero
bytes.

The KV cache is *paged* (vLLM-style) whenever the architecture allows it:
sequences map to fixed-size blocks of a shared physical pool through a
block table (``repro.cache``), so HBM is committed at block granularity
instead of a fixed ``[max_slots, s_max]`` rectangle. The per-block layout
keeps the head axis sharded over the tp-major model group — identical in
base and shift configs — so paging preserves the zero-copy SP↔TP switch.
Under data parallelism the engine pages PER dp row: slots partition into
``dp`` contiguous ranges, each row owns a private block pool (the pool's
leading block axis is sharded over the dp mesh axes, aligned with the
dp-sharded block-table batch, so row-local block ids index each shard's
local pool slice directly), and queued requests are routed to the row
with the most free blocks — FCFS within a row. Admission control holds
requests in the queue until their prompt fits in their row's free
blocks, and decode-time block exhaustion preempts the least-recently
scheduled request OF THE SAME ROW back to the queue (recompute-style,
its blocks are freed), which bounds memory while guaranteeing progress —
and isolates rows: pressure in one row can never evict another row's
requests or cached prefixes. Architectures with non-pageable state (MLA
latents, ring buffers, recurrent state) fall back to the contiguous
cache and pure slot admission; the fallback is recorded in
``paged_disabled_reason`` and surfaced via ``prefix_stats``/``step_log``
so a deployment can never lose paging silently.

With ``EngineConfig(prefix_cache=True)`` the paged cache gains automatic
prefix reuse (Arctic-Inference-style): full blocks of token ids are
indexed by chained hash (``repro.cache.PrefixIndex``) as prefill
completes them, and admission maps the longest indexed prefix of a new
prompt straight into the request's block table — prefill then starts at
the first uncached token, so ``ThresholdPolicy`` prices only the
*uncached* prefill work and heavy shared-prefix traffic stays below the
SP→TP shift threshold longer. Cached blocks are pinned by the index's
own reference: ``free_seq``/preemption decrement-not-free them, and an
LRU (leaf-first) eviction reclaims unpinned prefix blocks under memory
pressure. Writes into shared blocks (refcount > 1) go through
copy-on-write: the manager remaps the block and the engine applies the
physical copy to the device pool before the forward pass lands. The
index is per dp row (blocks never cross rows), routing is sticky across
preemptions so a request re-matches its own committed blocks, and an
in-flight registry shares concurrent same-prefix prefills: a request
whose next prompt block another admission is currently writing waits in
the queue and maps the block once committed instead of prefilling the
span again.

Scheduling on the paged cache is continuous batching with *mixed* batches
(Sarathi/Arctic-Inference-style): every iteration packs up to
``prefill_chunk`` prompt tokens per prefilling row PLUS all ready decode
rows into ONE forward pass (``Model.forward_fn``), so a prompt burst never
stalls in-flight decodes — the TPOT interference the serialized
prefill-OR-decode loop suffered. The shift policy sees the combined token
count (mixed batches are bigger, so Algorithm 2 reacts to real load) and
the device batch is compacted to the active rows and bucketed, instead of
padding every launch to ``max_slots``. The dense fallback (and
``mixed=False`` for A/B comparison) keeps the serialized iteration: each
step is either a prefill chunk batch or a decode batch. Shapes are
bucketed so each (config, shape) pair compiles once — the JAX analogue of
the paper's per-shape CUDA-graph capture."""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import (PagedKVCache, PrefixIndex, blocks_for_tokens,
                         pow2_bucket as _pow2)
from repro.core.policy import DEFAULT_SHIFT_THRESHOLD, ThresholdPolicy
from repro.ft.faults import FaultPlan, SnapshotError, corrupt_snapshot
from repro.ft.watchdog import StragglerWatchdog
from repro.models.model import Model
from repro.obs import Observability, NullObs, compile_count
from repro.parallel import Layout, layout_delta
from repro.spec import SpecConfig, SuffixDrafter
from .api import (BlockLedger, EngineStats, FaultConfig, ObsConfig,
                  PrefixConfig, PrefixStats)
from .deployment import Deployment, ReshardError, ReshardReport
from .request import FinishReason, Request

# Rolling-window length for the per-step audit records (the source the
# step_log/step_times/config_trace views derive from). Totals live in the
# metrics registry (steps_total, step_seconds histogram, ...) so
# long-running engines don't grow without bound.
TRACE_WINDOW = 1024

_EMPTY_STEP = {"prefill_tokens": 0, "decode_tokens": 0, "ready_decodes": 0,
               "attn_ctx_tokens": 0}


# the most padded tokens that ready decode rows may add to a mixed launch
# carrying prefill chunks (ShiftEngine._ride): on qwen3-8b over a 2x2 v5e a
# 16-row shift decode step (31.8 ms) cost what ~700-900 padded tokens of a
# 16 x 256 base step (186 ms) did
DECODE_RIDE_TOKENS = 1024


def balanced_chunk(left: int, chunk: int) -> int:
    """Tokens of a row's next prefill chunk, ``left`` tokens from its offset
    through its last known token. The fewest chunks that reach the last
    token's position share the tokens near-equally, so none is shorter than
    about half the chunk unless the whole remainder is (a short last chunk
    would let the threshold policy send a prefill step to the shift config).
    Where equal shares would overrun ``chunk`` the last token rides the
    decode path instead, as it does when fixed chunks end one token short."""
    n = -(-(left - 1) // chunk)
    if n <= 0:
        return 0
    q = -(-left // n)
    return q if q <= chunk else -(-(left - 1) // n)


class EngineConfig:
    """Engine configuration: scheduling/paging knobs flat, the accreted
    prefix/fault/observability flags grouped into nested dataclasses
    (:class:`~repro.engine.api.PrefixConfig`,
    :class:`~repro.engine.api.FaultConfig`,
    :class:`~repro.engine.api.ObsConfig`). The pre-PR-8 flat *write*
    kwargs (``prefix_cache=``, ``max_queue=``, ..., ``obs=bool``) were
    deprecated in PR 8 and are now removed — pass the nested groups. The
    flat *read* properties below stay."""

    def __init__(self, max_slots: int = 8, s_max: int = 256,
                 prefill_chunk: int = 64,
                 threshold: int = DEFAULT_SHIFT_THRESHOLD,
                 eos_id: int = -1,
                 # paged KV cache: None = auto (paged when supported);
                 # num_blocks counts physical blocks PER DP ROW incl. the
                 # row's null block, 0 = auto-size so slots×s_max fits
                 paged: Optional[bool] = None, block_size: int = 16,
                 num_blocks: int = 0,
                 # scheduling: None = mixed whenever paged; False keeps the
                 # serialized prefill-OR-decode iteration
                 mixed: Optional[bool] = None,
                 # repro.kernels.KernelConfig selecting the paged-attention
                 # backend (None = dispatch default)
                 kernel: Optional[object] = None,
                 # nested groups (each None = defaults)
                 prefix: Optional[PrefixConfig] = None,
                 fault: Optional[FaultConfig] = None,
                 obs: Optional[ObsConfig] = None,
                 # speculative decoding (repro.spec): k == 0 disables
                 spec: Optional[SpecConfig] = None):
        self.max_slots = max_slots
        self.s_max = s_max
        self.prefill_chunk = prefill_chunk
        self.threshold = threshold
        self.eos_id = eos_id
        self.paged = paged
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.mixed = mixed
        self.kernel = kernel
        if isinstance(obs, bool):
            raise TypeError("obs=bool was removed with the flat-kwarg "
                            "shim — pass obs=ObsConfig(enabled=...)")
        self.prefix = prefix if prefix is not None else PrefixConfig()
        self.fault = fault if fault is not None else FaultConfig()
        self.obs = obs if obs is not None else ObsConfig()
        self.spec = spec if spec is not None else SpecConfig()

    def __repr__(self):
        return (f"EngineConfig(max_slots={self.max_slots}, "
                f"s_max={self.s_max}, prefill_chunk={self.prefill_chunk}, "
                f"threshold={self.threshold}, paged={self.paged}, "
                f"block_size={self.block_size}, "
                f"num_blocks={self.num_blocks}, mixed={self.mixed}, "
                f"prefix={self.prefix}, fault={self.fault}, obs={self.obs}, "
                f"spec={self.spec})")

    @property
    def spec_k(self) -> int:
        return self.spec.k

    # flat read properties: the pre-PR-8 spellings, mapped onto the groups
    @property
    def prefix_cache(self) -> bool:
        return self.prefix.enabled

    @property
    def max_queue(self) -> int:
        return self.fault.max_queue

    @property
    def shed_policy(self) -> str:
        return self.fault.shed_policy

    @property
    def deadline_s(self) -> Optional[float]:
        return self.fault.deadline_s

    @property
    def quarantine_after(self) -> int:
        return self.fault.quarantine_after

    @property
    def retry_backoff(self) -> int:
        return self.fault.retry_backoff

    @property
    def auto_snapshot_every(self) -> int:
        return self.fault.auto_snapshot_every

    @property
    def snapshot_keep(self) -> int:
        return self.fault.snapshot_keep

    @property
    def straggler_factor(self) -> float:
        return self.fault.straggler_factor


class ShiftEngine:
    def __init__(self, model_base: Model, model_shift: Model,
                 params_base, params_shift, cfg: EngineConfig,
                 policy=None, now=time.monotonic,
                 faults: Optional[FaultPlan] = None):
        assert model_base.cfg is model_shift.cfg
        if cfg.shed_policy not in ("reject-newest", "evict-longest-queued"):
            raise ValueError(f"unknown shed_policy {cfg.shed_policy!r}")
        self.mcfg = model_base.cfg
        self.cfg = cfg
        self.policy = policy or ThresholdPolicy(cfg.threshold)
        # detect ONCE which of the per-iteration context facts
        # (ctx_tokens/n_rows/ctx_max) the policy's use_base accepts —
        # legacy 2-arg policies get none, partial signatures get exactly
        # what they declare. A per-call try/except TypeError would
        # swallow TypeErrors raised INSIDE a modern policy and silently
        # degrade it to the context-blind path.
        _facts = ("ctx_tokens", "n_rows", "ctx_max", "spec_tokens")
        try:
            params = inspect.signature(self.policy.use_base).parameters
            if any(p.kind is inspect.Parameter.VAR_KEYWORD
                   for p in params.values()):
                self._policy_ctx_kwargs = _facts
            else:
                self._policy_ctx_kwargs = tuple(k for k in _facts
                                                if k in params)
        except (TypeError, ValueError):      # builtins/callables w/o sig
            self._policy_ctx_kwargs = ()
        self.now = now

        dp = max(model_base.lay.dp, 1)
        reason = None
        if not model_base.supports_paged:
            reason = (f"architecture {self.mcfg.name} has non-pageable "
                      "layer kinds (MLA latents / ring buffers / recurrent "
                      "state keep the contiguous cache)")
        elif cfg.max_slots % dp != 0:
            reason = (f"max_slots={cfg.max_slots} not divisible by "
                      f"dp={dp} — slots partition into dp rows")
        if cfg.paged and reason is not None:
            raise ValueError(
                f"config {self.mcfg.name} cannot use a paged KV cache: "
                f"{reason}")
        self.paged = reason is None if cfg.paged is None else cfg.paged
        if not self.paged and reason is None:
            reason = "paged=False in EngineConfig"
        # why paging is off, if it is. The dense fallback must be LOUD: it
        # also disables mixed batching and prefix caching, so the reason is
        # surfaced in prefix_stats and every step_log entry — a dp-sharded
        # deployment can't silently lose paging again.
        self.paged_disabled_reason = None if self.paged else reason
        self.mixed = self.paged if cfg.mixed is None else cfg.mixed
        if self.mixed and not self.paged:
            raise ValueError(
                "mixed-batch stepping requires the paged KV cache (ragged "
                "rows scatter through the block table's null block)")
        if cfg.prefix_cache and not self.paged:
            raise ValueError(
                "prefix caching requires the paged KV cache (cached blocks "
                "are shared through ref-counted block tables)")
        # speculative decoding: drafts only flow on the paged mixed-batch
        # path (verify rows are ragged q_len=1+k rows through the paged
        # attention; the serialized/dense fallbacks decode one token per
        # pass, so their streams are trivially identical to spec-off).
        # Like the dense fallback, a silently inert spec_k must be LOUD.
        self.spec = cfg.spec
        self.spec_disabled_reason = None
        if self.spec.k and not (self.paged and self.mixed):
            self.spec_disabled_reason = (
                "speculative decoding requires the paged mixed-batch path "
                f"(paged={self.paged}, mixed={self.mixed})")
        self._spec_on = bool(self.spec.k) and self.spec_disabled_reason is None
        # drafter state is a pure function of each request's tokens and is
        # therefore never snapshotted: restore/reshard rebuild it lazily
        self.drafter = SuffixDrafter(self.spec)
        # reshard-aware admission: a scheduled reshard pauses admissions
        # for its lead steps so the re-pour moves fewer blocks
        self._pending_reshard: Optional[dict] = None
        self.last_reshard_report: Optional[ReshardReport] = None
        # set by a mixed step that left ready decode rows out (_ride): the
        # next step launches them alone
        self._decode_turn = False
        # ONE swappable value owns everything layout-dependent: the model
        # views, the sharded params, and the jit tables. reshard() replaces
        # it wholesale; base/shift/p_base/p_shift/dp/_forward/_prefill/
        # _decode below are read-through views of it.
        self.deploy = Deployment.build(model_base, model_shift,
                                       params_base, params_shift,
                                       mixed=self.mixed, paged=self.paged,
                                       kernel=cfg.kernel)
        self.slots_per_row = cfg.max_slots // dp if self.paged \
            else cfg.max_slots
        if self.paged:
            nmax = blocks_for_tokens(cfg.s_max, cfg.block_size)
            # cfg.num_blocks is PER dp row — each row owns a private pool
            # (and null block); the device pool concatenates the rows on
            # its dp-sharded leading axis
            row_blocks = cfg.num_blocks or self.slots_per_row * nmax + 1
            self.kv = PagedKVCache(row_blocks, cfg.block_size,
                                   cfg.max_slots, nmax, dp=self.dp)
            self.cache = model_base.init_paged_cache(row_blocks,
                                                     cfg.block_size)
            # persistent host mirror of the block tables; only rows the
            # PagedKVCache marks dirty are re-copied (satellite of the
            # full-rebuild-per-step fix)
            self._bt_host = np.zeros((cfg.max_slots, nmax), np.int32)
            if cfg.prefix_cache:
                # one index per dp row: physical blocks never cross rows,
                # so neither can cached prefixes — row pressure can only
                # evict that row's entries
                self.prefix_rows = [
                    PrefixIndex(cfg.block_size, self.kv.allocators[r])
                    for r in range(self.dp)]
                self.kv.prefix_indices = list(self.prefix_rows)
            else:
                self.prefix_rows = None
            # in-flight prefix registry, one dict per row: chain hash of
            # every full prompt block an admitted request will write ->
            # its rid. A same-prefix admission probes it and waits for the
            # writer's commit instead of prefilling the span again.
            self._inflight: List[dict] = [dict() for _ in range(self.dp)]
            # pending (src, dst) pool-global block copies from
            # copy-on-write; applied to the device pool in one batched
            # scatter before the next forward pass launches
            self._step_copies: List[tuple] = []
            self._cow_fn = jax.jit(self._cow_body, donate_argnums=(0,))
        else:
            self.kv = None
            self.prefix_rows = None
            self.cache = model_base.init_cache(cfg.max_slots, cfg.s_max)
        self.cow_copies = 0
        self.lens = np.zeros((cfg.max_slots,), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * cfg.max_slots
        self.queue: List[Request] = []
        self.step_count = 0
        self.preemptions = 0
        # facade registry: every submitted request by rid, so stream(rid)/
        # request(rid) resolve after retirement too (short-lived engines;
        # a long-running deployment would bound this)
        self._requests: Dict[int, Request] = {}
        # replica id under a cluster Router (None standalone); stamped on
        # every step record and event through the obs surface
        self.replica: Optional[int] = None
        # fault tolerance: the (optional) deterministic fault schedule, the
        # per-step straggler watchdog, the retained recovery snapshots, and
        # the graceful-shutdown flag (draining stops fresh admissions)
        self.faults = faults
        self.watchdog = StragglerWatchdog(factor=cfg.straggler_factor)
        self._snap_ring: List[dict] = []
        self._alloc_fault_armed = False
        self.draining = False
        # ONE observability surface (repro.obs): metrics registry +
        # lifecycle event log + the rolling per-step audit records that the
        # legacy step_log/step_times/config_trace views derive from. Each
        # record carries the monotone step index and its duration, so the
        # views can never desynchronize under window trimming again.
        self.obs = (Observability("engine", window=cfg.obs.window, now=now,
                                  event_cap=cfg.obs.event_cap)
                    if cfg.obs.enabled else NullObs(now=now))
        if self.prefix_rows is not None:
            self._attach_prefix_observers()
        # composition + shift-audit facts of the step in flight, stashed by
        # _log_step/_choose and folded into one record in step()
        self._step_stats: Optional[dict] = None
        self._step_audit: Optional[dict] = None
        # the launched shape and KV coverage, set when a step launches
        self._step_launch: Optional[dict] = None

    # ------------------------------------------- deployment (read-through)
    # Everything layout-dependent lives on self.deploy so reshard() can
    # swap it as one value; these views keep the engine body (and its
    # callers) spelled the same as before the refactor.
    @property
    def base(self) -> Model:
        return self.deploy.base

    @property
    def shift(self) -> Model:
        return self.deploy.shift

    @property
    def p_base(self):
        return self.deploy.p_base

    @property
    def p_shift(self):
        return self.deploy.p_shift

    @property
    def dp(self) -> int:
        return self.deploy.dp

    @property
    def _forward(self):
        return self.deploy.forward

    @property
    def _prefill(self):
        return self.deploy.prefill

    @property
    def _decode(self):
        return self.deploy.decode

    # ---------------------------------------------------- observability
    def _attach_prefix_observers(self):
        """Point every row index's eviction callback at the event log
        (re-run after restore — from_state builds fresh indexes)."""
        for r, idx in enumerate(self.prefix_rows):
            idx.on_evict = self._make_evict_observer(r)

    def _make_evict_observer(self, row: int):
        def observer(n_blocks: int):
            self.obs.inc("prefix_evictions_total", n_blocks)
            self.obs.emit("prefix_evict", step=self.step_count,
                          blocks=n_blocks, row=row)
        return observer

    # Legacy views, all derived from the one rolling store of per-step
    # audit records (each record carries its own monotone step index and
    # duration, so entries of any two views always join on "step") and the
    # metrics registry. No parallel bookkeeping to drift.
    @property
    def step_log(self) -> List[dict]:
        return list(self.obs.step_records)

    @property
    def step_times(self) -> List[float]:
        return [r["dur_s"] for r in self.obs.step_records]

    @property
    def config_trace(self) -> List[str]:
        return [r["config"] for r in self.obs.step_records
                if r["config"] is not None]

    @property
    def config_counts(self) -> dict:
        reg = self.obs.registry
        return {"base": int(reg.counter_value("steps_total", config="base")),
                "shift": int(reg.counter_value("steps_total",
                                               config="shift"))}

    @property
    def total_step_time(self) -> float:
        return self.obs.registry.histogram_sum("step_seconds")

    @property
    def trace_window(self) -> int:
        return self.obs.window

    @trace_window.setter
    def trace_window(self, window: int):
        self.obs.window = window
        if len(self.obs.step_records) > window:
            del self.obs.step_records[:len(self.obs.step_records) - window]

    # ---------------------------------------------------------------- admin
    def add_request(self, req: Request):
        worst = len(req.prompt) + req.max_new_tokens
        if worst > self.cfg.s_max:
            raise ValueError(f"request {req.rid} exceeds s_max={self.cfg.s_max}")
        if self.paged and (blocks_for_tokens(worst, self.cfg.block_size)
                           > self.kv.num_blocks_per_row - 1):
            raise ValueError(
                f"request {req.rid} can never fit: needs "
                f"{blocks_for_tokens(worst, self.cfg.block_size)} blocks, "
                f"each dp row's pool has {self.kv.num_blocks_per_row - 1}")
        if req.deadline is None and self.cfg.deadline_s is not None:
            req.deadline = req.arrival + self.cfg.deadline_s
        self.queue.append(req)
        self._requests[req.rid] = req
        self.obs.inc("requests_arrived_total")
        self.obs.emit("queued", step=self.step_count, rid=req.rid,
                      prompt_tokens=len(req.prompt),
                      max_new_tokens=req.max_new_tokens,
                      arrival=req.arrival)
        if self.draining:
            # shutting down: accepted-but-terminal, never scheduled
            self._retire(req, FinishReason.SHED)
            return
        self._enforce_queue_bound(req)

    def _enforce_queue_bound(self, newest: Request):
        """Bounded admission queue: when the number of UNADMITTED requests
        exceeds ``max_queue``, the shed policy picks who terminates with
        ``FinishReason.SHED`` instead of the queue growing without bound —
        a traffic spike degrades into explicit rejections, not an
        ever-longer tail of doomed waiters."""
        if not self.cfg.max_queue:
            return
        waiting = [q for q in self.queue if q.slot is None]
        while len(waiting) > self.cfg.max_queue:
            if self.cfg.shed_policy == "reject-newest":
                victim = newest
            else:                      # evict-longest-queued
                victim = min(waiting, key=lambda q: (q.arrival, q.rid))
            self._retire(victim, FinishReason.SHED)
            waiting.remove(victim)
            if victim is newest:
                break

    # ------------------------------------------------------ typed outcomes
    _REASON_EVENT = {FinishReason.TIMEOUT: "timeout",
                     FinishReason.CANCELLED: "cancelled",
                     FinishReason.SHED: "shed",
                     FinishReason.FAILED: "quarantined"}
    _REASON_COUNTER = {FinishReason.TIMEOUT: "requests_timeout_total",
                       FinishReason.CANCELLED: "requests_cancelled_total",
                       FinishReason.SHED: "requests_shed_total",
                       FinishReason.FAILED: "requests_failed_total"}

    def _release_slot(self, req: Request):
        """Return ``req``'s slot and blocks to the engine without touching
        its token state (shared by preemption and terminal retirement).
        Leak-free by construction: in-flight prefix registrations are
        dropped and block refcounts decremented through ``free_seq`` (index
        pins survive — cached prefixes outlive the request)."""
        self._unregister_inflight(req)
        if self.paged:
            self.kv.free_seq(req.slot)
        self.slot_req[req.slot] = None
        self.lens[req.slot] = 0
        req.slot = None

    def _retire(self, req: Request, reason: FinishReason,
                t: Optional[float] = None):
        """Terminate ``req`` with a non-OK typed outcome (OK goes through
        ``_finish_token``). Every submitted request ends here or there —
        the engine never drops a request without a FinishReason."""
        assert reason is not FinishReason.OK
        t = self.now() if t is None else t
        if req.slot is not None:
            self._release_slot(req)
        self.queue = [q for q in self.queue if q.rid != req.rid]
        req.finish_time = t
        req.finish_reason = reason
        self.drafter.drop(req.rid)
        self.obs.inc(self._REASON_COUNTER[reason])
        self.obs.emit(self._REASON_EVENT[reason], step=self.step_count,
                      ts=t, rid=req.rid, row=req.row,
                      n_out=len(req.generated),
                      fail_count=req.fail_count)

    def cancel(self, rid: int) -> bool:
        """Explicitly terminate a queued or running request. Frees its
        blocks and prefix pins without leaks; returns False when ``rid``
        is not live (already terminal or never submitted)."""
        req = next((q for q in self.queue if q.rid == rid), None)
        if req is None:
            return False
        self._retire(req, FinishReason.CANCELLED)
        return True

    def _expire_deadlines(self):
        """Enforce per-request deadlines (checked every step): a request
        whose deadline passed terminates TIMEOUT whether it is still
        queued or mid-decode — a stuck or starved request can never hold
        its slot and blocks forever."""
        t = self.now()
        for req in list(self.queue):
            if req.deadline is not None and t > req.deadline:
                self._retire(req, FinishReason.TIMEOUT, t=t)

    # ------------------------------------------------------ fault injection
    def _fault_fired(self, fault):
        self.obs.inc("faults_injected_total", seam=fault.seam)
        self.obs.emit("fault_injected", step=self.step_count,
                      seam=fault.seam, fault_kind=fault.kind, row=fault.row)

    def _arm_step_faults(self):
        """Consult the fault plan once per step for the seams injected at
        step granularity: an ``alloc`` fault makes the step's FIRST
        ensure/COW attempt fail like a BlockOOM; a ``route`` fault fails
        one dp row — its active requests are preempted back to the queue
        (recompute) and enter step-counted retry backoff."""
        self._alloc_fault_armed = False
        if self.faults is None:
            return
        f = self.faults.at(self.step_count, "alloc")
        if f is not None:
            self._alloc_fault_armed = True
            self._fault_fired(f)
        f = self.faults.at(self.step_count, "route")
        if f is not None:
            self._fault_fired(f)
            victims = [r for r in self.active
                       if self.kv.row_of(r.slot) == f.row] if self.paged \
                else list(self.active)
            # recompute-retry only exists on the paged path (preemption is
            # a paged-cache mechanism); the dense fallback backs off in
            # place
            self._fail_requests(victims, preempt=self.paged)

    def _take_alloc_fault(self) -> bool:
        """True exactly once per armed step — the injected OOM."""
        if self._alloc_fault_armed:
            self._alloc_fault_armed = False
            return True
        return False

    def _fail_requests(self, reqs, preempt: bool = False):
        """Charge each request one step failure. At ``quarantine_after``
        accumulated failures the request terminates FAILED (fail the
        request, not the engine); below it the request backs off
        ``retry_backoff * fail_count`` steps (step-counted, deterministic)
        before it may be batched or re-admitted, optionally losing its
        slot (recompute-retry for a failed dp row)."""
        for r in reqs:
            r.fail_count += 1
            if r.fail_count >= self.cfg.quarantine_after:
                self._retire(r, FinishReason.FAILED)
                continue
            r.retry_at = self.step_count + 1 \
                + self.cfg.retry_backoff * r.fail_count
            if preempt and r.slot is not None:
                self._preempt(r)
            self.obs.inc("retries_total")
            self.obs.emit("retry", step=self.step_count, rid=r.rid,
                          fail_count=r.fail_count, retry_at=r.retry_at,
                          recompute=preempt)

    def _fail_step(self, reqs, n_ready: int, attn_ctx: int):
        """Account one failed forward step: the batch's requests enter
        retry/quarantine and the step record carries ``failed=True`` with
        ZERO token progress (exactly-once accounting — failed launches
        produce no tokens; ``attn_ctx`` stays nonzero for a poisoned-but-
        executed launch, whose attention reads really happened)."""
        self._step_fail_flag = True
        self._fail_requests(reqs)
        self._log_step(0, 0, n_ready, attn_ctx)

    def _retryable(self, r: Request) -> bool:
        """False while a previously failed request serves its backoff."""
        return r.retry_at <= self.step_count

    def _admissible(self, r: Request) -> bool:
        """Queue-side gate: backoff applies to (re)admission too, and a
        draining engine only re-admits requests that already held a slot
        (preempted in-flight work finishes; fresh work is shed)."""
        if not self._retryable(r):
            return False
        if self.draining and r.num_preemptions == 0 and not r.generated:
            return False
        return True

    # ----------------------------------------------------------- dp routing
    def _route(self, req: Request):
        """Assign a queued request to a dp row, free-block-aware: minimize
        routed-but-unadmitted demand MINUS allocatable blocks (free +
        prefix-reclaimable), ties to the lowest row id. Pending demand is
        part of the primary score, not a tie-break: a whole burst is
        routed before any admission updates the free lists, so scoring on
        free blocks alone would send the entire burst to the single
        freest row (``ServeSim._route`` prices placement the same way).
        Sticky: a preempted request keeps its row — its committed prefix
        blocks live in that row's pool, so re-admission there re-matches
        them."""
        if req.row is not None:
            return
        pend = [0] * self.dp
        for q in self.queue:
            if q.slot is None and q.row is not None:
                pend[q.row] += blocks_for_tokens(q.total_tokens + 1,
                                                 self.cfg.block_size)

        def score(r):
            free = self.kv.allocators[r].num_free
            idx = self.kv.prefix_indices[r]
            if idx is not None:
                free += idx.reclaimable()
            return (pend[r] - free, r)

        req.row = min(range(self.dp), key=score)
        self.obs.emit("routed", step=self.step_count, rid=req.rid,
                      row=req.row)

    def _register_inflight(self, req: Request, row: int, n_matched: int):
        """Publish the chain hash of every full prompt block this
        admission will write (depths past its prefix match), so a
        same-prefix request admitted behind it can wait for the commit
        instead of prefilling the shared span again."""
        bs = self.cfg.block_size
        keys = []
        for i, key in enumerate(PrefixIndex.chain_keys(
                req.all_tokens(), bs, (req.total_tokens - 1) // bs)):
            if i >= n_matched:
                self._inflight[row][key] = req.rid
                keys.append(key)
        req.inflight_keys = keys

    def _unregister_inflight(self, req: Request):
        if not self.paged or req.row is None or not req.inflight_keys:
            return
        m = self._inflight[req.row]
        for k in req.inflight_keys:
            if m.get(k) == req.rid:
                del m[k]
        req.inflight_keys = []

    def _wait_for_inflight(self, req: Request, row: int, matched) -> bool:
        """True when another request in this row is mid-prefill over the
        next full block of ``req``'s prompt: its chain hash (one depth
        past ``req``'s committed match) is registered in the row's
        in-flight map and the writer has not yet written it. ``req`` then
        stays queued — once the writer's block commits, the normal match
        maps it and ``req`` prefills only past the shared span."""
        if self.prefix_rows is None:
            return False
        bs = self.cfg.block_size
        i = len(matched)
        if (i + 1) * bs > req.total_tokens - 1:
            return False                 # no further full block to share
        *_, key = PrefixIndex.chain_keys(req.all_tokens(), bs, i + 1)
        wrid = self._inflight[row].get(key)
        if wrid is None:
            return False
        w = next((a for a in self.active if a.rid == wrid), None)
        if w is None or w is req or w.done:
            self._inflight[row].pop(key, None)         # stale entry
            return False
        # writer already wrote the block but the match didn't extend: the
        # commit was stopped (hash collision) and never will cover it —
        # don't wait on it (livelock guard)
        return w.prefilled < (i + 1) * bs

    def _admit(self):
        """Assign queue slots FCFS per dp row. Unrouted requests are first
        routed to the row with the most free blocks; slots of row r are
        the contiguous range [r*slots_per_row, (r+1)*slots_per_row).
        Paged: a request is admitted only when its whole (re)prompt plus
        one decode token fits in its row's free blocks (counting blocks a
        prefix match already covers and blocks LRU eviction of the row's
        prefix index could reclaim) — the memory-pressure gate that lets
        arbitrarily many requests queue against a small pool. On admission
        the longest indexed prefix of the (re)prompt is mapped into the
        slot's block table, so prefill starts at the first uncached token.
        One FCFS exception: a request voluntarily waiting on an in-flight
        same-prefix prefill is skipped, not blocking — its wait is bounded
        by the writer's progress, so later arrivals may admit past it."""
        if self._pending_reshard is not None:
            # admissions hold while a scheduled reshard counts down, so
            # the swap re-pours only already-running requests' blocks
            self._pending_reshard["paused"] += 1
            return
        if not self.paged:
            for req in list(self.queue):
                if req.slot is not None or not self._admissible(req):
                    continue
                slot = next((s for s, owner in enumerate(self.slot_req)
                             if owner is None), None)
                if slot is None:
                    break
                req.slot = slot
                self.slot_req[slot] = req
                self.lens[slot] = req.prefilled
                self._on_admit(req)
            return
        for req in self.queue:
            if req.slot is None:
                self._route(req)
        spr = self.slots_per_row
        for row in range(self.dp):
            for req in list(self.queue):
                if req.slot is not None or req.row != row \
                        or not self._admissible(req):
                    continue
                slot = next((s for s in range(row * spr, (row + 1) * spr)
                             if self.slot_req[s] is None), None)
                if slot is None:
                    break
                idx = self.prefix_rows[row] if self.prefix_rows else None
                matched = []
                if idx is not None:
                    # probe WITHOUT the LRU bump: a queue head that
                    # repeatedly fails admission must not refresh its
                    # matched entries' recency (that would skew leaf-first
                    # LRU eviction toward blocks nobody has mapped). Cap
                    # at total-1: the last known token always runs through
                    # the forward pass to produce the next logits.
                    matched = idx.match(req.all_tokens(),
                                        max_tokens=req.total_tokens - 1,
                                        bump=False)
                    if self._wait_for_inflight(req, row, matched):
                        continue
                if not self.kv.can_allocate(req.total_tokens + 1,
                                            cached_blocks=matched, row=row):
                    break                   # FCFS within the row
                req.slot = slot
                self.slot_req[slot] = req
                if idx is not None:
                    idx.record(len(matched))
                    self.obs.inc("prefix_hits_total" if matched
                                 else "prefix_misses_total")
                    if matched:
                        idx.bump(req.all_tokens(), len(matched))
                        self.kv.assign_prefix(slot, matched)
                        req.prefilled = len(matched) * self.cfg.block_size
                        req.cached_tokens = req.prefilled
                        self.obs.inc("prefix_tokens_saved_total",
                                     req.prefilled)
                        self.obs.emit("prefix_hit", step=self.step_count,
                                      rid=req.rid, row=row,
                                      blocks=len(matched),
                                      tokens=req.prefilled)
                    self._register_inflight(req, row, len(matched))
                if self._take_alloc_fault() \
                        or not self.kv.ensure(slot, req.total_tokens + 1):
                    # allocation failed past the can_allocate probe (an
                    # injected OOM, or eviction reclaiming less than
                    # estimated): admission must be atomic, so roll it
                    # back — prefix refs taken by assign_prefix are
                    # decremented by free_seq, the in-flight registration
                    # is dropped, and the request stays queued (FCFS: the
                    # row stops admitting this step)
                    self._unregister_inflight(req)
                    if self.kv.n_mapped[slot]:
                        self.kv.free_seq(slot)
                    self.slot_req[slot] = None
                    req.slot = None
                    req.prefilled = 0
                    req.cached_tokens = 0
                    break
                self.lens[slot] = req.prefilled
                self._on_admit(req)

    def _on_admit(self, req: Request):
        """Record one (re)admission: span event + queue-time histogram.
        Re-admissions after preemption count again — queue time under
        memory pressure is part of what the paper's E2E numbers see."""
        self.obs.inc("requests_admitted_total")
        ts = self.now()
        queue_s = max(ts - req.arrival, 0.0)
        self.obs.observe("queue_seconds", queue_s)
        self.obs.emit("admitted", step=self.step_count, ts=ts, rid=req.rid,
                      row=req.row, slot=req.slot, queue_s=queue_s,
                      cached_tokens=req.cached_tokens,
                      preemptions=req.num_preemptions)

    @property
    def active(self) -> List[Request]:
        return [r for r in self.slot_req if r is not None]

    @property
    def prefix(self):
        """Row-0 prefix index — the only one under dp=1 (single-row
        deployments, most tests). Use ``prefix_rows`` under dp>1."""
        return self.prefix_rows[0] if self.prefix_rows else None

    @property
    def prefix_stats(self) -> PrefixStats:
        """Prefix-cache counters summed across dp rows (zeros when caching
        is off) plus the engine's COW copy count and — so dense fallbacks
        are observable — the reason paging is off (None when paged).
        Typed and frozen; ``["hits"]``/``.as_dict()`` keep the old dict
        call sites working."""
        s = {"entries": 0, "hits": 0, "misses": 0, "tokens_saved": 0,
             "evictions": 0}
        for idx in (self.prefix_rows or []):
            for k, v in idx.stats().items():
                s[k] += v
        return PrefixStats(cow_copies=self.cow_copies,
                           paged_disabled_reason=self.paged_disabled_reason,
                           **s)

    # ----------------------------------------------------- memory pressure
    def _preempt(self, victim: Request):
        """Evict a running request back to the queue, freeing its blocks.
        Recompute-style: its prompt+generated re-prefills on re-admission
        (into the same dp row — ``row`` is sticky)."""
        self._unregister_inflight(victim)
        row, slot = self.kv.row_of(victim.slot), victim.slot
        self.kv.free_seq(victim.slot)
        self.slot_req[victim.slot] = None
        self.lens[victim.slot] = 0
        victim.slot = None
        victim.prefilled = 0
        victim.cached_tokens = 0
        victim.pc_blocks, victim.pc_parent = 0, None   # recommit from root
        victim.num_preemptions += 1
        self.preemptions += 1
        self.obs.inc("requests_preempted_total")
        self.obs.emit("preempted", step=self.step_count, rid=victim.rid,
                      row=row, slot=slot,
                      tokens_generated=len(victim.generated))

    def _reserve(self, req: Request, n_tokens: int, protect,
                 write_from: Optional[int] = None) -> bool:
        """Grow req's block table to cover n_tokens — and, when
        ``write_from`` is given, copy-on-write any shared block in the
        write range ``[write_from, n_tokens)`` — LRU-preempting other
        active requests *in the same dp row* if the row's free list (plus
        its prefix-index eviction) runs dry. Physical blocks never cross
        rows, so pressure in one row can never evict another row's
        requests or pinned prefixes. Returns False when nothing outside
        ``protect`` can be evicted. COW block copies are queued on
        ``_step_copies``; the caller applies them to the device pool
        before the forward pass."""
        row = self.kv.row_of(req.slot)
        while True:
            # an armed alloc fault fails this step's first ensure/COW
            # attempt exactly like a BlockOOM would — the recovery path
            # below (victim preemption, then retry) is the code under test
            if not self._take_alloc_fault() \
                    and self.kv.ensure(req.slot, n_tokens):
                if write_from is None:
                    return True
                ok, copies = self.kv.copy_on_write(req.slot, write_from,
                                                   n_tokens)
                if ok:
                    self._step_copies.extend(copies)
                    return True
            victims = [a for a in self.active
                       if a is not req and a not in protect
                       and self.kv.row_of(a.slot) == row]
            if not victims:
                return False
            self._preempt(min(victims,
                              key=lambda a: (a.last_used, -a.arrival)))

    @staticmethod
    def _cow_body(cache, src, dst):
        """Batched physical block copy (COW data plane): pool[dst] =
        pool[src] on every cached layer. Body-stack leaves carry a leading
        layer-repeat axis, so the block axis is found by rank. Padding
        pairs are (0, 0) null-block self-copies. All gathers read the
        pre-copy pool (gather-then-scatter), so a block freed by
        preemption and reallocated as another copy's dst in the same step
        still sources its original bytes."""
        def cp(pool):
            if pool.ndim == 5:      # [reps, num_blocks, slots, bs, Dh]
                return pool.at[:, dst].set(pool[:, src])
            return pool.at[dst].set(pool[src])
        return jax.tree.map(cp, cache)

    def _apply_copies(self):
        """Flush queued COW copies to the device pool (one batched op)."""
        if not self._step_copies:
            return
        pairs = self._step_copies
        self._step_copies = []
        self.cow_copies += len(pairs)
        self.obs.inc("cow_copies_total", len(pairs))
        self.obs.emit("cow_flush", step=self.step_count, copies=len(pairs))
        n = _pow2(len(pairs))
        src = np.zeros((n,), np.int32)      # padding: null-block self-copy
        dst = np.zeros((n,), np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i], dst[i] = s, d
        self.cache = self._cow_fn(self.cache, jnp.asarray(src),
                                  jnp.asarray(dst))

    def _commit_prefix(self, req: Request):
        """Index every fully-written block of ``req`` (token positions
        ``0..prefilled-1`` are in the cache) so later requests sharing the
        prefix reuse it. Called before the request could release its
        blocks; already-indexed chunks are only LRU-bumped. Incremental:
        the per-request ``(pc_blocks, pc_parent)`` cursor means a decode
        step hashes at most one new chunk instead of re-walking the chain
        from the root (which would be O(len^2) over a request's life)."""
        if self.prefix_rows is None or req.slot is None:
            return
        idx = self.prefix_rows[self.kv.row_of(req.slot)]
        full = min(req.prefilled // self.cfg.block_size,
                   int(self.kv.n_mapped[req.slot]))
        if full > req.pc_blocks:
            req.pc_blocks, req.pc_parent, _ = idx.commit_incremental(
                req.all_tokens(), req.pc_blocks, full, req.pc_parent,
                self.kv.seq_blocks(req.slot))

    def _refresh_block_tables(self):
        """Sync the persistent host mirror: re-copy only rows whose tables
        changed since the last step (growth, free, fork)."""
        for s in self.kv.take_dirty():
            self._bt_host[s] = self.kv.table[s]

    def _block_tables(self, rows: List[Request]) -> np.ndarray:
        """Device block-table batch for the serialized path, from the host
        mirror. Rows outside this batch stay all-null so their (garbage)
        scatter lands in the null block."""
        self._refresh_block_tables()
        bt = np.zeros((self.cfg.max_slots, self.kv.max_blocks_per_seq),
                      np.int32)
        idx = [r.slot for r in rows]
        bt[idx] = self._bt_host[idx]
        return bt

    # ---------------------------------------------------------------- steps
    def _choose(self, n_tokens: int, n_prefill: int,
                ctx_tokens: int = 0, n_rows: int = 0,
                ctx_max: int = 0, spec_tokens: int = 0) -> str:
        """Pick the config for this iteration. ``ctx_tokens`` is the sum of
        the batch rows' ACTUAL context lengths — what the
        work-proportional kernel reads — and ``ctx_max`` the largest row
        (the pow2 launch bucket derives from it), so a cost-model policy
        prices the real KV traffic instead of assuming S_max.
        ``spec_tokens`` counts the speculative draft queries inside
        ``n_tokens``: they add weight-side compute like prefill tokens but
        share their row's KV read, so an acceptance-aware policy prices
        verify-vs-decode instead of treating each as a full decode row.
        Policies with the older two-arg signature still work (they just
        don't see the context)."""
        facts = {"ctx_tokens": ctx_tokens, "n_rows": n_rows,
                 "ctx_max": ctx_max, "spec_tokens": spec_tokens}
        use_base = self.policy.use_base(
            n_tokens, n_prefill,
            **{k: facts[k] for k in self._policy_ctx_kwargs})
        name = "base" if use_base else "shift"
        # shift-decision audit: the chosen config AND exactly the facts the
        # policy saw, folded into this step's record by step() — a base<->
        # shift flip is explainable from the trace alone
        self._step_audit = {"config": name, "n_tokens": n_tokens,
                            "ctx_tokens": ctx_tokens, "n_rows": n_rows,
                            "ctx_max": ctx_max,
                            "threshold": getattr(self.policy, "threshold",
                                                 None)}
        if spec_tokens:
            self._step_audit["spec_tokens"] = spec_tokens
        return name

    def _log_step(self, n_prefill: int, n_decode: int, n_ready: int,
                  attn_ctx: int = 0):
        # attn_ctx_tokens = sum of the actual per-row context lengths this
        # forward attended — the work-proportionality witness: a trace
        # alone can verify iteration cost tracks occupancy, not s_max.
        # Stashed here, folded into ONE schema-checked step record (with
        # the monotone step index, duration, and shift audit) in step().
        self._step_stats = {"prefill_tokens": n_prefill,
                            "decode_tokens": n_decode,
                            "ready_decodes": n_ready,
                            "attn_ctx_tokens": attn_ctx}

    def _finish_token(self, r: Request, tok: int, t: float):
        """Append a sampled token and retire the request if it is done."""
        r.generated.append(tok)
        # the forward wrote this step's input tokens through position
        # r.pos-1, so the cache covers everything before the new last token
        r.prefilled = r.pos
        if r.first_token_time is None:
            r.first_token_time = t
            ttft = max(t - r.arrival, 0.0)
            self.obs.observe("ttft_seconds", ttft)
            self.obs.emit("first_token", step=self.step_count, ts=t,
                          rid=r.rid, ttft_s=ttft)
        self.lens[r.slot] = r.pos
        if r.done or (self.cfg.eos_id >= 0
                      and r.generated[-1] == self.cfg.eos_id):
            r.finish_time = t
            r.finish_reason = FinishReason.OK
            self.drafter.drop(r.rid)
            if self.paged:
                self._unregister_inflight(r)
                self.kv.free_seq(r.slot)
            self.slot_req[r.slot] = None
            self.queue = [q for q in self.queue if q.rid != r.rid]
            n_out = len(r.generated)
            e2e = max(t - r.arrival, 0.0)
            tpot = ((t - r.first_token_time) / (n_out - 1)
                    if n_out > 1 else None)
            self.obs.inc("requests_finished_total")
            self.obs.observe("e2e_seconds", e2e)
            if tpot is not None:
                self.obs.observe("tpot_seconds", tpot)
            self.obs.emit("finish", step=self.step_count, ts=t, rid=r.rid,
                          row=r.row, n_out=n_out, n_prompt=len(r.prompt),
                          ttft_s=max(r.first_token_time - r.arrival, 0.0),
                          tpot_s=tpot, e2e_s=e2e,
                          cached_tokens=r.cached_tokens,
                          preemptions=r.num_preemptions)

    # -------------------------------------------------------- mixed stepping
    def _run_mixed(self) -> bool:
        """One fused iteration: every ready decode row PLUS a prefill chunk
        for every row still swallowing its (re)prompt, in a single forward
        pass. Decode rows reserve blocks first — a prompt burst can shrink
        the prefill side but never starve in-flight decodes (it can only
        lose rows to preemption under real memory pressure). A prefill row
        whose chunk reaches its last known token samples its next token in
        the same pass (fused prefill→first-token, one fewer iteration per
        request)."""
        with self.obs.span("engine.plan"):
            C = self.cfg.prefill_chunk
            ready = [r for r in self.active if self._prefill_done(r)
                     and not r.done and self._retryable(r)]
            n_ready = len(ready)
            decode_turn, self._decode_turn = self._decode_turn, False
            rows = []                          # (req, off, q_len, produces)
            drafts: Dict[int, List[int]] = {}  # rid -> speculative drafts
            decode_rows = set()             # Requests batched as decode rows
            protect = set()
            for r in ready:
                if r.slot is None:
                    continue               # preempted by an earlier reserve
                # coverage for the token written this step (position r.pos)
                if self._reserve(r, r.total_tokens, protect=protect,
                                 write_from=r.pos):
                    d: List[int] = []
                    if self._spec_on:
                        # draft at most the tokens this request can still
                        # emit beyond the one it samples anyway, so accepted
                        # drafts never overrun max_new_tokens or s_max
                        d = self.drafter.propose(
                            r.rid, r.all_tokens(),
                            r.max_new_tokens - len(r.generated) - 1)
                        # the draft extension must never preempt anyone or
                        # evict cached prefixes — speculation is
                        # opportunistic; shrink the draft until the row's
                        # free list covers it (stage-1 COW already
                        # privatized the block holding r.pos; extension
                        # blocks are freshly allocated)
                        while d and not self.kv.ensure(
                                r.slot, r.total_tokens + len(d)):
                            d.pop()
                    if d:
                        drafts[r.rid] = d
                    rows.append((r, r.pos, 1 + len(d), True))
                    decode_rows.add(r)
                    protect.add(r)
            n_decode = len(decode_rows)
            n_spec = sum(len(d) for d in drafts.values())
            n_prefill_tok = 0
            for r in ([] if decode_turn and rows else list(self.active)):
                if r.slot is None or r.done or self._prefill_done(r) \
                        or not self._retryable(r):
                    continue
                off = r.prefilled
                end = off + balanced_chunk(r.total_tokens - off, C)
                if end <= off:
                    continue
                if not self._reserve(r, end, protect=protect, write_from=off):
                    continue
                # the chunk runs through the LAST known token: when it reaches
                # the end, this pass also samples the row's next token
                rows.append((r, off, end - off, end == r.total_tokens))
                protect.add(r)
                n_prefill_tok += end - off
                self.obs.emit("prefill_chunk", step=self.step_count, rid=r.rid,
                              off=off, tokens=end - off)
            if not rows:
                self._log_step(0, 0, n_ready)
                return False
            if n_prefill_tok and decode_rows and not drafts:
                rows, decode_rows = self._ride(rows, decode_rows, C)
                n_decode = len(decode_rows)

            attn_ctx = sum(off + ql for _, off, ql, _ in rows)
            mode = self._choose(n_prefill_tok + n_decode + n_spec,
                                n_prefill_tok, attn_ctx, len(rows),
                                max(off + ql for _, off, ql, _ in rows),
                                spec_tokens=n_spec)
            model = self.base if mode == "base" else self.shift
            params = self.p_base if mode == "base" else self.p_shift
            # compact to active rows; bucket every axis so each (config, shape)
            # compiles once. The chunk axis must stay divisible by the chosen
            # config's sp degree (decode-only batches on the shift config are
            # [R, 1] — no padded rectangle). Under dp>1 the device batch axis
            # is sharded over dp, so each dp row's requests must land in that
            # row's contiguous segment; every row gets the same pow2-bucketed
            # segment width so the sharded shape stays rectangular (a row with
            # no work this step contributes an all-padding segment whose
            # scatters land in its null block). A decode-only step launches
            # every slot's row: it costs about one read of the weights
            # whatever its rows, so it keeps one shape per config.
            full = not n_prefill_tok and not drafts
            Cb = max(_pow2(max(ql for _, _, ql, _ in rows)),
                     max(model.lay.sp, 1))
            if n_prefill_tok:
                # chunks run from about C/2 to C tokens (balanced_chunk), a
                # short prompt's from 1: launching every step that carries
                # a prefill chunk at the chunk width keeps one chunk bucket
                # per config, so a warm-up can cover the shapes a window
                # meets
                Cb = max(Cb, _pow2(C))
            placed, Rb = self._place(
                rows, full, Cb if n_prefill_tok and not drafts else 0)
        with self.obs.span("engine.inputs"):
            self._refresh_block_tables()
            # every launch carries the whole table width: the kernel (and its
            # mirror) skips grid steps past each row's occupancy, so the width
            # costs only those empty steps, and with one width per config a
            # warm-up has only the row buckets to compile
            nmax = self.kv.max_blocks_per_seq
            toks = np.zeros((Rb, Cb), np.int32)
            qlen = np.zeros((Rb,), np.int32)
            offs = np.zeros((Rb,), np.int32)
            bt = np.zeros((Rb, nmax), np.int32)
            for i, (r, off, ql, _) in placed:
                if ql == 1 and off == r.pos:   # decode row: O(1) last token
                    toks[i, 0] = (r.generated[-1] if r.generated
                                  else r.prompt[-1])
                elif off == r.pos:             # spec row: last token + draft
                    toks[i, 0] = (r.generated[-1] if r.generated
                                  else r.prompt[-1])
                    toks[i, 1:ql] = drafts[r.rid]
                else:
                    toks[i, :ql] = r.all_tokens()[off:off + ql]
                qlen[i] = ql
                offs[i] = off
                bt[i] = self._bt_host[r.slot]
            self._apply_copies()           # COW copies land before the write
            args = [jnp.asarray(toks), jnp.asarray(qlen), jnp.asarray(offs),
                    jnp.asarray(bt)]
            # speculative verify width: the extraction returns each row's last
            # n_last sampled tokens. No drafts -> n_last == 1 -> the exact
            # (bitwise) non-speculative compiled program.
            n_last = (_pow2(1 + max(len(d) for d in drafts.values()))
                      if drafts else 1)
            fwd = self.deploy.forward_at(mode, n_last)
        fault = (self.faults.at(self.step_count, "forward")
                 if self.faults is not None else None)
        if fault is not None:
            self._fault_fired(fault)
        if fault is None or fault.kind == "nan":
            # "nan" models poisoned logits: the launch runs (and rewrites
            # the same KV bytes a retry will), but its outputs are garbage
            with self.obs.span("engine.launch"):
                nxt, self.cache = fwd(params, self.cache, *args,
                                      *self._extras(Rb))
            self._step_launch = {
                "bucket": [Rb, Cb, nmax],
                "kv_blocks": sum(
                    blocks_for_tokens(off + ql, self.cfg.block_size)
                    for _, (_, off, ql, _) in placed),
                "kv_step_blocks": model.attn_step_blocks(
                    Cb, nmax, self.cfg.block_size)}
            with self.obs.span("engine.wait"):
                nxt = np.asarray(nxt)
        if fault is not None:
            # failed step: no token is applied, no progress is recorded —
            # every batched request retries with backoff or quarantines.
            # A retry recomputes the identical chunk (KV writes are
            # position-idempotent), so streams stay bit-identical. Draft
            # extensions are unmapped so the failed step leaves block
            # accounting exactly as a non-speculative failure would (the
            # retry re-proposes the identical drafts and re-ensures).
            for r in decode_rows:
                if r.rid in drafts and r.slot is not None:
                    self.kv.truncate(r.slot, r.total_tokens)
            self._fail_step([e[0] for _, e in placed], n_ready,
                            attn_ctx if fault.kind == "nan" else 0)
            return True
        with self.obs.span("engine.apply"):
            t = self.now()
            n_dec_emit = 0          # decode-side tokens actually delivered
            n_accepted = 0          # accepted draft tokens across spec rows
            rollback_blocks = 0
            for i, (r, off, ql, produces) in placed:
                r.last_used = self.step_count
                d = drafts.get(r.rid) if r in decode_rows else None
                if d is None:
                    r.prefilled = off + ql
                    self.lens[r.slot] = r.prefilled
                    self._commit_prefix(r)     # before a finish frees the slot
                    if produces:
                        tok = int(nxt[i, n_last - 1]) if n_last > 1 \
                            else int(nxt[i])
                        self._finish_token(r, tok, t)
                        if r in decode_rows:
                            n_dec_emit += 1
                    continue
                # speculative verify: row outputs o_0..o_m sit in the last
                # m+1 extraction columns; accept the longest prefix where
                # draft j matched output j-1, then emit o_0..o_accepted —
                # exactly the tokens sequential greedy decode would produce
                m = len(d)
                out = [int(nxt[i, n_last - 1 - m + j]) for j in range(m + 1)]
                n_acc = 0
                while n_acc < m and d[n_acc] == out[n_acc]:
                    n_acc += 1
                emitted = out[:n_acc + 1]
                # roll back rejected-draft KV first, while the slot is alive:
                # a logical truncate of the uncommitted tail blocks (kept
                # blocks' junk positions are masked by the context length and
                # overwritten position-idempotently by later steps)
                rollback_blocks += self.kv.truncate(r.slot, off + len(emitted))
                delivered = 0
                for j, tok in enumerate(emitted):
                    # commit BEFORE each append with the coverage a sequential
                    # step would have had (prefilled never exceeds the tokens
                    # known at commit time, so the index hashes no draft junk)
                    r.prefilled = off + j + 1
                    self.lens[r.slot] = r.prefilled
                    self._commit_prefix(r)
                    self._finish_token(r, tok, t)
                    delivered = j + 1
                    if r.finish_reason is not None:
                        break                  # eos mid-accept: rest discarded
                n_dec_emit += delivered
                n_accepted += delivered - 1
                self.obs.observe("spec_accepted_per_row", delivered - 1)
            self._log_step(n_prefill_tok, n_dec_emit, n_ready, attn_ctx)
            if n_spec:
                self._step_stats["spec_proposed"] = n_spec
                self._step_stats["spec_accepted"] = n_accepted
                self.obs.inc("spec_proposed_total", n_spec)
                if n_accepted:
                    self.obs.inc("spec_accepted_total", n_accepted)
                if rollback_blocks:
                    self.obs.inc("spec_rollback_blocks_total", rollback_blocks)
            return True

    def _place(self, rows, full: bool = False, chunk: int = 0):
        """Launch row of each batch row, and the launch's row count: pow2
        bucketed, per dp row segment under dp>1; ``full`` launches every
        slot's row. A launch that carries prefill chunks at chunk bucket
        ``chunk`` takes the fewest rows that hold its own among those a
        ``forward`` program has already run at that chunk, where there
        are such: a bucket built mid-serving stalls every row for seconds,
        while padded rows cost a share of one step, and decode rows may
        ride in them (``_ride``)."""
        if self.dp > 1:
            per = [[] for _ in range(self.dp)]
            for e in rows:
                per[self.kv.row_of(e[0].slot)].append(e)
            seg = self._built_rows(self.dp * _pow2(
                self.slots_per_row if full
                else max(len(p) for p in per)), chunk) // self.dp
            return ([(ri * seg + j, e) for ri, p in enumerate(per)
                     for j, e in enumerate(p)], self.dp * seg)
        return (list(enumerate(rows)), self._built_rows(
            _pow2(self.cfg.max_slots if full else len(rows)), chunk))

    def _built_rows(self, rows: int, chunk: int) -> int:
        """The fewest rows, at least ``rows`` and a multiple of dp, of a
        launch the deployment has run at ``chunk``; ``rows`` where there is
        none or ``chunk`` is 0."""
        fit = [r for r, c in self.deploy.built if chunk and c == chunk
               and r >= rows and r % self.dp == 0]
        return min(fit, default=rows)

    def _ride(self, rows, decode_rows, chunk: int):
        """Which ready decode rows ride in a launch that carries prefill
        chunks. Every row of that launch is padded to the chunk width, so
        a decode row there costs a chunk of compute, while a decode-only
        launch costs about one read of the weights whatever its rows. The
        decode rows all ride while the padded tokens they add stay within
        ``DECODE_RIDE_TOKENS``, whatever configs the two launches would
        run. Otherwise only those that fit the rows the prefill rows pad
        anyway ride, and the next step launches the decode rows alone
        (under the threshold policy: prefill in base, decode in shift).
        Rows are counted in the launch's row bucket (``_place``), which may
        be a larger bucket already built. Returns the rows to launch and
        the decode rows among them."""
        cb = _pow2(chunk)
        pre = [e for e in rows if e[0] not in decode_rows]
        rb_pre = self._place(pre, chunk=cb)[1]
        if (self._place(rows, chunk=cb)[1] - rb_pre) * cb \
                <= DECODE_RIDE_TOKENS:
            return rows, decode_rows
        keep = list(pre)
        for e in rows:
            if e[0] in decode_rows \
                    and self._place(keep + [e], chunk=cb)[1] == rb_pre:
                keep.append(e)
        riding = {e[0] for e in keep} & decode_rows
        self._decode_turn = True
        return ([e for e in rows
                 if e[0] not in decode_rows or e[0] in riding], riding)

    # --------------------------------------------------- serialized stepping
    def _run_prefill(self):
        """One chunked-prefill iteration over slots that still need their
        (re)prompt — after a preemption, prompt+generated re-prefill here."""
        with self.obs.span("engine.plan"):
            C = self.cfg.prefill_chunk
            todo = [r for r in self.active
                    if not self._prefill_done(r) and self._retryable(r)]
            if not todo:
                return False
            toks = np.zeros((self.cfg.max_slots, C), np.int32)
            # dummy rows: dense cache -> scratch tail (their writes must not
            # land on live offsets); paged -> offset 0 (their scatter routes to
            # the null block regardless, and a zero context keeps the
            # work-proportional kernel from looping s_max/bs null blocks)
            offs = np.full((self.cfg.max_slots,),
                           0 if self.paged else max(self.cfg.s_max - C, 0),
                           np.int32)
            rows = []
            # MLA latent caches assume a uniform offset across the chunk batch
            uniform = self.mcfg.mla is not None
            base_off = None
            for r in todo:
                if r.slot is None:
                    continue               # preempted by an earlier reserve
                off = r.prefilled
                if uniform and base_off is not None and off != base_off:
                    continue
                # the final known token is fed through the decode path instead
                seq = r.all_tokens()
                chunk = seq[off:min(off + C, len(seq) - 1)]
                if not chunk:
                    continue
                if self.paged and not self._reserve(
                        r, off + len(chunk), protect={rr for rr, _ in rows},
                        write_from=off):
                    continue
                toks[r.slot, :len(chunk)] = chunk
                offs[r.slot] = off
                rows.append((r, len(chunk)))
                base_off = off
                self.obs.emit("prefill_chunk", step=self.step_count, rid=r.rid,
                              off=off, tokens=len(chunk))
            if not rows:
                return False
            n_tok = sum(n for _, n in rows)
            # what the attention path actually reads this launch: the paged
            # kernel attends ctx = offset + C for EVERY batch row (the chunk
            # buffer is q_lens == C wide, padding columns included, and the
            # max_slots - len(rows) dummy rows attend a C-long null
            # context) — logging only the real tokens would understate the
            # occupancy witness and the policy's pricing. The dense
            # fallback makes no work-proportionality claim; its log keeps
            # the real-token sum.
            if self.paged:
                attn_ctx = sum(r.prefilled + C for r, _ in rows) \
                    + (self.cfg.max_slots - len(rows)) * C
                ctx_max = max(r.prefilled + C for r, _ in rows)
            else:
                attn_ctx = sum(r.prefilled + n for r, n in rows)
                ctx_max = max(r.prefilled + n for r, n in rows)
            mode = self._choose(n_tok, n_tok, attn_ctx, len(rows), ctx_max)
        with self.obs.span("engine.inputs"):
            params = self.p_base if mode == "base" else self.p_shift
            extras = self._extras(self.cfg.max_slots)
            args = [jnp.asarray(toks), jnp.asarray(offs)]
            if self.paged:
                args.append(jnp.asarray(
                    self._block_tables([r for r, _ in rows])))
                self._apply_copies()
        fault = (self.faults.at(self.step_count, "forward")
                 if self.faults is not None else None)
        if fault is not None:
            self._fault_fired(fault)
        if fault is None or fault.kind == "nan":
            with self.obs.span("engine.launch"):
                _, self.cache = self._prefill[mode](params, self.cache,
                                                    *args, *extras)
            self._step_launch = self._serial_launch(
                mode, args, [r.prefilled + n for r, n in rows])
        if fault is not None:
            self._fail_step([r for r, _ in rows],
                            sum(1 for r in self.active
                                if self._prefill_done(r) and not r.done),
                            attn_ctx if fault.kind == "nan" else 0)
            return True
        with self.obs.span("engine.apply"):
            for r, n in rows:
                r.prefilled += n
                r.last_used = self.step_count
                self.lens[r.slot] = r.prefilled
                self._commit_prefix(r)
            self._log_step(n_tok, 0,
                           sum(1 for r in self.active
                               if self._prefill_done(r) and not r.done),
                           attn_ctx)
            return True

    def _prefill_done(self, r) -> bool:
        return r.prefilled >= r.pos

    def _run_decode(self):
        with self.obs.span("engine.plan"):
            ready = [r for r in self.active
                     if self._prefill_done(r) and not r.done
                     and self._retryable(r)]
            n_ready = len(ready)
            if self.paged:
                kept = []
                for r in ready:
                    if r.slot is None:
                        continue           # preempted by an earlier reserve
                    # coverage for the token written this step (position r.pos)
                    if self._reserve(r, r.total_tokens, protect=set(kept),
                                     write_from=r.pos):
                        kept.append(r)
                ready = kept
            if not ready:
                return False
            # inactive slots in the always-max_slots decode batch each read one
            # null-block position (ctx = lens + 1 = 1) on the paged kernel path
            attn_ctx = sum(r.pos + 1 for r in ready) \
                + (self.cfg.max_slots - len(ready) if self.paged else 0)
            mode = self._choose(len(ready), 0, attn_ctx, len(ready),
                                max(r.pos + 1 for r in ready))
        with self.obs.span("engine.inputs"):
            params = self.p_base if mode == "base" else self.p_shift
            toks = np.zeros((self.cfg.max_slots,), np.int32)
            lens = np.zeros((self.cfg.max_slots,), np.int32)
            for r in ready:
                toks[r.slot] = (r.generated[-1] if r.generated
                                else r.prompt[-1])
                lens[r.slot] = r.pos           # write position of this token
            args = [jnp.asarray(toks), jnp.asarray(lens)]
            if self.paged:
                args.append(jnp.asarray(self._block_tables(ready)))
                self._apply_copies()
        fault = (self.faults.at(self.step_count, "forward")
                 if self.faults is not None else None)
        if fault is not None:
            self._fault_fired(fault)
        if fault is None or fault.kind == "nan":
            with self.obs.span("engine.launch"):
                nxt, self.cache = self._decode[mode](params, self.cache,
                                                     *args)
            self._step_launch = self._serial_launch(
                mode, args, [r.pos + 1 for r in ready])
            with self.obs.span("engine.wait"):
                nxt = np.asarray(nxt)
        if fault is not None:
            self._fail_step(list(ready), n_ready,
                            attn_ctx if fault.kind == "nan" else 0)
            return True
        with self.obs.span("engine.apply"):
            t = self.now()
            for r in ready:
                r.last_used = self.step_count
                r.prefilled = r.pos + 1        # this step wrote position r.pos
                self._commit_prefix(r)
                self._finish_token(r, int(nxt[r.slot]), t)
            self._log_step(0, len(ready), n_ready, attn_ctx)
            return True

    def _serial_launch(self, mode, args, ctx) -> dict:
        """Launch record of a serialized step: the full-slot batch's shape
        (table width 0 on the dense cache) and, when paged, the KV blocks
        covering each launched row's context ``ctx`` after the write and
        the attention kernel's KV blocks per grid step."""
        toks = args[0]
        rec = {"bucket": [toks.shape[0], toks.shape[1] if toks.ndim > 1
                          else 1, args[2].shape[1] if self.paged else 0]}
        if self.paged:
            bs = self.cfg.block_size
            rec["kv_blocks"] = sum(blocks_for_tokens(c, bs) for c in ctx)
            model = self.base if mode == "base" else self.shift
            rec["kv_step_blocks"] = model.attn_step_blocks(
                rec["bucket"][1], rec["bucket"][2], bs)
        return rec

    def _extras(self, batch: int):
        ex = []
        if self.mcfg.frontend == "vision_stub":
            ex.append(jnp.zeros((batch, self.mcfg.frontend_seq,
                                 self.mcfg.d_model), self.base.dtype))
        if self.mcfg.encoder_layers:
            ex.append(jnp.zeros((batch, self.mcfg.encoder_seq,
                                 self.mcfg.d_model), self.base.dtype))
        return ex

    def step(self) -> bool:
        """One engine iteration. Returns False when idle. The iteration is
        the ``engine.step`` span (carrying the step index); its phases are
        the ``engine.*`` spans inside, whose host seconds land in the
        record's ``host_s``."""
        with self.obs.span("engine.step", step=self.step_count):
            return self._step()

    def _step(self) -> bool:
        t0 = self.now()
        c0 = compile_count()
        self._step_stats = None
        self._step_audit = None
        self._step_launch = None
        self._step_fail_flag = False
        with self.obs.span("engine.admit"):
            # fault-tolerance pre-pass: deadlines first (an expired request
            # must not consume this step's batch space), then the step's
            # scheduled faults (route faults preempt before admission
            # refills the failed row's slots)
            self._expire_deadlines()
            self._arm_step_faults()
            if self._pending_reshard is not None \
                    and self._pending_reshard["countdown"] <= 0:
                # lead steps served with admissions paused; execute the
                # swap now, before this step admits into the old layout
                p, self._pending_reshard = self._pending_reshard, None
                rep = self.reshard(p["layout"], mesh=p["mesh"],
                                   row_blocks=p["row_blocks"])
                self.last_reshard_report = dataclasses.replace(
                    rep, admission_paused_steps=p["paused"])
            elif self._pending_reshard is not None:
                self._pending_reshard["countdown"] -= 1
            self._admit()
        if self.mixed:
            # fused prefill+decode batch: no iteration-granularity
            # interference between a prompt burst and in-flight decodes
            progressed = self._run_mixed()
        else:
            # prefill-priority with chunking; decode otherwise (chunked
            # prefill interleaves at iteration granularity)
            progressed = self._run_prefill() or self._run_decode()
        dt = self.now() - t0
        # ONE audit record per iteration: monotone step index + duration +
        # batch composition + the shift-decision audit, all in one entry
        # (config is None for steps that launched nothing)
        rec = {"step": self.step_count, "t_start": t0, "dur_s": dt,
               "config": None, **(self._step_stats or _EMPTY_STEP)}
        if self._step_audit is not None:
            rec.update(self._step_audit)
        if self._step_launch is not None:
            rec.update(self._step_launch)
        rec["compiles"] = compile_count() - c0
        if self.obs.phases:
            rec["host_s"] = dict(self.obs.phases)
        if self._step_fail_flag:
            rec["failed"] = True
            self.obs.inc("failed_steps_total")
        if self.paged_disabled_reason is not None:
            # the dense fallback must be visible in the step log, not just
            # at construction: dp-sharded deployments silently lost paging
            # (and mixed batching + prefix caching with it) once already
            rec["paged_disabled_reason"] = self.paged_disabled_reason
        self.obs.record_step(rec)
        if self.watchdog.observe(dt):
            self.obs.inc("straggler_steps_total")
            self.obs.emit("straggler", step=self.step_count, dur_s=dt,
                          flagged=self.watchdog.flagged)
        self.obs.set_gauge("queue_depth",
                           sum(1 for q in self.queue if q.slot is None))
        self.obs.set_gauge("active_requests", len(self.active))
        if self.paged:
            self.obs.set_gauge("free_blocks", self.kv.num_free_blocks)
        self.step_count += 1
        if self.cfg.auto_snapshot_every \
                and self.step_count % self.cfg.auto_snapshot_every == 0:
            self._auto_snapshot()
        return progressed

    def run_until_idle(self, max_steps: int = 10000):
        for _ in range(max_steps):
            if not self.step():
                if not self.queue and not self.active:
                    break
        return self

    # ------------------------------------------------------- fault tolerance
    def snapshot(self):
        """Engine state for checkpoint/restart (weights are static).
        Observability state rides along: counters stay monotone and
        in-flight request spans resume across a restore (the snapshot
        event itself is emitted first, so it is part of the capture)."""
        self.obs.emit("snapshot", step=self.step_count)
        self.obs.inc("snapshots_total")
        snap = {
            "cache": jax.tree.map(np.asarray, self.cache),
            "lens": self.lens.copy(),
            "step_count": self.step_count,
            # layout identity: a snapshot only restores into a deployment
            # with the same (dp, sp, tp, ep) signature (validate_snapshot)
            "layout": tuple(self.deploy.layout.signature),
            "obs": self.obs.state_dict(),
            "requests": [
                {"rid": r.rid, "prompt": list(r.prompt), "slot": r.slot,
                 "row": r.row,
                 "prefilled": r.prefilled, "generated": list(r.generated),
                 "max_new_tokens": r.max_new_tokens, "arrival": r.arrival,
                 "deadline": r.deadline,
                 "first_token_time": r.first_token_time,
                 "finish_time": r.finish_time, "last_used": r.last_used,
                 "cached_tokens": r.cached_tokens,
                 "num_preemptions": r.num_preemptions,
                 "fail_count": r.fail_count, "retry_at": r.retry_at}
                for r in self.queue + [x for x in self.slot_req
                                       if x is not None and x not in self.queue]],
        }
        if self.paged:
            snap["kv"] = self.kv.state_dict()
            if self.prefix_rows is not None:
                # the per-row allocator snapshots carry the indexes' pins —
                # every row's index must round-trip with them or those refs
                # would leak
                snap["prefix"] = [idx.state_dict()
                                  for idx in self.prefix_rows]
        if self.faults is not None:
            f = self.faults.at(self.step_count, "snapshot")
            if f is not None:
                # the snapshot seam corrupts the CAPTURE; detection happens
                # at recovery time (validate_snapshot), forcing recover()
                # to fall back to an older retained snapshot
                self._fault_fired(f)
                corrupt_snapshot(snap, self.step_count)
        return snap

    def _auto_snapshot(self):
        """Periodic checkpoint into the retained ring (the durable-storage
        stand-in the crash-recovery drill restores from)."""
        self._snap_ring.append(self.snapshot())
        del self._snap_ring[:-self.cfg.snapshot_keep]

    def validate_snapshot(self, snap) -> None:
        """Raise :class:`SnapshotError` if ``snap`` cannot be restored by
        THIS engine. Called by ``restore`` before any mutation, so a
        truncated/corrupted checkpoint leaves the engine untouched."""
        if not isinstance(snap, dict):
            raise SnapshotError(f"snapshot is {type(snap).__name__}, "
                                "not a dict")
        for key in ("cache", "lens", "requests"):
            if key not in snap:
                raise SnapshotError(f"snapshot missing required key {key!r}")
        lens = snap["lens"]
        if getattr(lens, "shape", None) != (self.cfg.max_slots,):
            raise SnapshotError(
                f"snapshot lens shape {getattr(lens, 'shape', None)} != "
                f"engine max_slots ({self.cfg.max_slots},)")
        seen_slots = set()
        for rd in snap["requests"]:
            if not isinstance(rd, dict):
                raise SnapshotError("request entry is not a dict")
            for key in ("rid", "prompt", "slot", "prefilled", "generated",
                        "max_new_tokens"):
                if key not in rd:
                    raise SnapshotError(
                        f"request entry missing required key {key!r}")
            slot = rd["slot"]
            if slot is not None:
                if not (0 <= slot < self.cfg.max_slots):
                    raise SnapshotError(f"request slot {slot} out of range "
                                        f"[0, {self.cfg.max_slots})")
                if slot in seen_slots:
                    raise SnapshotError(f"duplicate request slot {slot}")
                seen_slots.add(slot)
        if "layout" in snap:
            sig = tuple(self.deploy.layout.signature)
            if tuple(snap["layout"]) != sig:
                raise SnapshotError(
                    f"snapshot was captured under layout signature "
                    f"{tuple(snap['layout'])} (dp, sp, tp, ep); this "
                    f"engine's deployment is {sig} — reshard first, or "
                    "restore into a matching deployment")
        if self.paged:
            if "kv" not in snap:
                raise SnapshotError("paged engine restoring a snapshot "
                                    "without the paged-KV state")
            if snap["kv"].get("dp", 1) != self.dp:   # pre-layout snapshots
                raise SnapshotError(
                    f"snapshot has dp={snap['kv'].get('dp', 1)}, "
                    f"engine has dp={self.dp}")
            if self.prefix_rows is not None:
                # the per-row allocator snapshots carry the indexes' pins —
                # restoring one without the other leaks every pinned block
                if "prefix" not in snap:
                    raise SnapshotError(
                        "prefix-caching engine restoring a snapshot without "
                        "the indexes (their allocator pins would leak)")
                if len(snap["prefix"]) != self.dp:
                    raise SnapshotError(
                        f"snapshot has {len(snap['prefix'])} prefix indexes, "
                        f"engine has dp={self.dp}")
            elif "prefix" in snap:
                raise SnapshotError(
                    "snapshot carries prefix indexes but this engine has "
                    "prefix_cache=False (their allocator pins would leak)")

    def recover(self, snapshots=None):
        """Crash recovery: restore the newest snapshot that validates,
        falling back through older retained ones (a scheduled snapshot
        fault corrupts a capture; the ring absorbs it). Raises
        :class:`SnapshotError` when nothing restorable remains."""
        ring = self._snap_ring if snapshots is None else list(snapshots)
        for snap in reversed(ring):
            try:
                self.validate_snapshot(snap)
            except SnapshotError:
                continue
            self.restore(snap)
            self.obs.inc("recoveries_total")
            self.obs.emit("recovered", step=self.step_count,
                          n_requests=len(self.queue))
            return self
        raise SnapshotError("no valid snapshot to recover from")

    def restore(self, snap):
        """Rebuild engine state from ``snapshot()``. Validates first and
        raises :class:`SnapshotError` on a malformed/corrupted snapshot
        WITHOUT touching engine state. The in-flight prefill registry is
        intentionally NOT restored (worst case: one duplicated shared-span
        prefill right after a restart)."""
        self.validate_snapshot(snap)
        self.cache = jax.tree.map(jnp.asarray, snap["cache"])
        self.lens = snap["lens"].copy()
        # observability resumes where the snapshot left off: counters stay
        # monotone, event spans of in-flight requests keep their history,
        # and the step index continues instead of restarting at 0 (older
        # snapshots without these keys restore with fresh zeroed state)
        self.step_count = snap.get("step_count", 0)
        if snap.get("obs") is not None and self.obs.enabled:
            self.obs.load_state(snap["obs"])
        if self.paged:
            # presence/shape of kv+prefix already checked by
            # validate_snapshot, so the mutation below cannot half-apply
            self.kv = PagedKVCache.from_state(snap["kv"])
            if self.prefix_rows is not None:
                self.prefix_rows = [
                    PrefixIndex.from_state(s, self.kv.allocators[r])
                    for r, s in enumerate(snap["prefix"])]
                self.kv.prefix_indices = list(self.prefix_rows)
                self._attach_prefix_observers()
            self._inflight = [dict() for _ in range(self.dp)]
            self._refresh_block_tables()   # from_state marks all rows dirty
        self.slot_req = [None] * self.cfg.max_slots
        self.queue = []
        self._requests = {}
        # drafter state is a pure function of each request's tokens: a
        # fresh drafter rebuilds lazily from all_tokens() and proposes
        # exactly what the pre-crash one would have (never snapshotted)
        self.drafter.reset()
        for rd in snap["requests"]:
            r = Request(rd["rid"], rd["prompt"], rd["max_new_tokens"],
                        arrival=rd.get("arrival", 0.0))
            r.slot = rd["slot"]
            r.row = rd.get("row")
            r.prefilled = rd["prefilled"]
            r.generated = list(rd["generated"])
            r.deadline = rd.get("deadline")
            r.first_token_time = rd.get("first_token_time")
            r.finish_time = rd.get("finish_time")
            r.last_used = rd.get("last_used", 0)
            r.cached_tokens = rd.get("cached_tokens", 0)
            r.num_preemptions = rd.get("num_preemptions", 0)
            r.fail_count = rd.get("fail_count", 0)
            r.retry_at = rd.get("retry_at", 0)
            if r.slot is not None:
                self.slot_req[r.slot] = r
            self.queue.append(r)
            self._requests[r.rid] = r
        self.obs.emit("restore", step=self.step_count)
        return self

    def drain(self, max_steps: int = 10000, release_cache: bool = True):
        """Graceful shutdown: finish in-flight decodes, shed requests that
        never got a slot, accept nothing new. With ``release_cache`` the
        prefix pins are dropped too, so afterwards the block accounting is
        exactly zero (the chaos drills assert it)."""
        self.draining = True
        for r in [q for q in self.queue if q.slot is None
                  and q.num_preemptions == 0 and not q.generated]:
            self._retire(r, FinishReason.SHED)
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        # anything still queued after the step budget (quarantine-backoff
        # stragglers, preempted requests that never re-fit) is shed — the
        # terminal-outcome contract holds even on a bounded shutdown
        for r in list(self.queue):
            self._retire(r, FinishReason.SHED)
        if release_cache and self.prefix_rows is not None:
            for idx in self.prefix_rows:
                idx.evict(len(idx))
        return self

    def block_accounting(self) -> BlockLedger:
        """Paged-block ledger for leak checks: ``used`` counts per-sequence
        mappings, ``pinned`` counts prefix-index pins. Both must be zero
        after ``drain()`` — any remainder is a leaked block. Typed and
        frozen; compares equal to the old ``{"used": .., "pinned": ..}``
        dicts when ``free``/``free_per_row`` are defaulted."""
        if not self.paged:
            return BlockLedger()
        return BlockLedger(
            used=self.kv.num_used_blocks,
            pinned=sum(len(idx.blocks())
                       for idx in (self.prefix_rows or [])),
            free=self.kv.num_free_blocks,
            free_per_row=tuple(self.kv.row_free_blocks(r)
                               for r in range(self.dp)))

    # --------------------------------------------------- elastic resharding
    def schedule_reshard(self, layout: Layout, mesh=None,
                         row_blocks: int = 0, lead_steps: int = 1):
        """Plan a reshard ``lead_steps`` iterations ahead: admissions
        pause immediately (so the swap re-pours only the blocks of
        already-running requests, not a last-moment admission burst) and
        the swap itself executes at the start of the target step. The
        resulting :class:`ReshardReport` — with
        ``admission_paused_steps`` counting the held iterations — lands
        in ``last_reshard_report``. A step-0 schedule (``lead_steps=0``)
        reshards on the very next step with no paused admissions."""
        if self._pending_reshard is not None:
            raise ReshardError("a reshard is already scheduled")
        if lead_steps < 0:
            raise ValueError(f"lead_steps must be >= 0, got {lead_steps}")
        self._pending_reshard = {"layout": layout, "mesh": mesh,
                                 "row_blocks": row_blocks,
                                 "countdown": lead_steps, "paused": 0}
        self.obs.emit("reshard_scheduled", step=self.step_count,
                      lead_steps=lead_steps)

    def reshard(self, layout: Layout, mesh=None,
                row_blocks: int = 0) -> ReshardReport:
        """Swap the engine onto a new parallel layout between iterations.

        The protocol is validate -> plan -> mutate: every check that can
        fail runs against read-only state first, so a raised
        :class:`ReshardError` leaves the engine serving on its current
        deployment. The mutation then (1) flushes pending COW copies and
        exports every slot-holder's committed blocks to host memory, (2)
        swaps the :class:`Deployment` (weights re-place through
        ``ft/elastic.reshard_params`` — bitwise for same-shape leaves),
        (3) rebuilds the paged pool in the new dp-row geometry
        (``row_blocks`` per row; 0 = preserve total usable capacity), and
        (4) re-pours the holders: deterministic best-fit placement into
        the new rows, block payloads written back at their new pool-global
        ids, recorded as PR 8's typed :class:`TransferOp` plan
        (replica-local: src == dst replica). Queued non-holders are
        re-routed from scratch; prefix indexes restart empty (the dropped
        pin count is reported); retained snapshots from the old layout
        stay in the ring and fail ``validate_snapshot`` with a typed
        :class:`SnapshotError` rather than restoring into the wrong
        geometry.

        Mid-decode streams resume bit-identically: block bytes move
        verbatim and the dp-row change never re-orders a sequence's
        positions. (Changing tp changes the logits' psum order — argmax
        streams stay stable on the reduced test models, but that is a
        determinism-in-practice property, not an algebraic one.)"""
        from repro.cluster.migration import build_transfer_plan
        if not self.paged:
            raise ReshardError(
                "resharding requires the paged KV cache "
                f"({self.paged_disabled_reason})")
        delta = layout_delta(self.deploy.layout, layout)
        new_dp = max(layout.dp, 1)
        if self.cfg.max_slots % new_dp != 0:
            raise ReshardError(
                f"max_slots={self.cfg.max_slots} not divisible by the new "
                f"dp={new_dp} — slots partition into dp rows")
        if delta.kind == "same":
            return ReshardReport(delta, 0, 0, 0)
        old_dp = self.dp
        old_rb = self.kv.num_blocks_per_row
        bs = self.cfg.block_size
        nmax = self.kv.max_blocks_per_seq
        # default: preserve total usable (non-null) block capacity
        new_rb = row_blocks or (old_dp * (old_rb - 1)) // new_dp + 1
        new_spr = self.cfg.max_slots // new_dp
        # ---------------- validate + plan (read-only; ReshardError-safe)
        holders = [r for r in self.slot_req if r is not None]
        for q in self.queue:
            worst = max(q.total_tokens + 1,
                        len(q.prompt) + q.max_new_tokens)
            if blocks_for_tokens(worst, bs) > new_rb - 1:
                raise ReshardError(
                    f"request {q.rid} needs {blocks_for_tokens(worst, bs)} "
                    f"blocks; each new dp row's pool has {new_rb - 1}")
        free = [new_rb - 1] * new_dp
        slots_left = [new_spr] * new_dp
        placement = {}                     # rid -> (row, slot, n_blocks)
        for r in sorted(holders,
                        key=lambda r: (-int(self.kv.n_mapped[r.slot]),
                                       r.rid)):
            need = int(self.kv.n_mapped[r.slot])
            fits = [ri for ri in range(new_dp)
                    if slots_left[ri] > 0 and free[ri] >= need]
            if not fits:
                raise ReshardError(
                    f"cannot place request {r.rid} ({need} blocks) into "
                    f"layout {layout.describe()} — shrink exceeds "
                    "per-row capacity")
            row = max(fits, key=lambda ri: (free[ri], -ri))
            slot = (row + 1) * new_spr - slots_left[row]
            slots_left[row] -= 1
            free[row] -= need
            placement[r.rid] = (row, slot, need)
        blocks_moved = sum(n for _, _, n in placement.values())
        self.obs.emit("reshard_begin", step=self.step_count,
                      old=self.deploy.layout.describe(),
                      new=layout.describe(), delta_kind=delta.kind,
                      requests=len(holders), blocks=blocks_moved)
        # ---------------- export (host copies of every holder's blocks)
        self._apply_copies()               # pending COW lands first
        exports = {}
        for r in holders:
            row = self.kv.row_of(r.slot)
            gids = np.asarray([self.kv.global_block(row, b)
                               for b in self.kv.seq_blocks(r.slot)],
                              np.int32)

            def take(pool, gids=gids):
                arr = np.asarray(pool)
                return (arr[:, gids].copy() if arr.ndim == 5
                        else arr[gids].copy())

            exports[r.rid] = {
                "state": {"rid": r.rid, "prefilled": r.prefilled},
                "block_size": bs,
                "src_blocks": [int(g) for g in gids],
                "payload": jax.tree.map(take, self.cache)}
        dropped_pins = sum(len(idx.blocks())
                           for idx in (self.prefix_rows or []))
        # ---------------- swap the deployment + pool geometry
        old_base, old_shift = self.deploy.base, self.deploy.shift
        new_base = Model(cfg=self.mcfg, lay=layout, mesh=mesh,
                         dtype=old_base.dtype, kernel=old_base.kernel)
        new_shift = Model(cfg=self.mcfg, lay=layout.to_shift(), mesh=mesh,
                          dtype=old_shift.dtype, kernel=old_shift.kernel)
        self.deploy = self.deploy.reshard(new_base, new_shift)
        self.kv = PagedKVCache(new_rb, bs, self.cfg.max_slots, nmax,
                               dp=new_dp)
        self.cache = new_base.init_paged_cache(new_rb, bs)
        self.slots_per_row = new_spr
        self.slot_req = [None] * self.cfg.max_slots
        self.lens[:] = 0
        self._bt_host = np.zeros((self.cfg.max_slots, nmax), np.int32)
        self._step_copies = []
        self._inflight = [dict() for _ in range(new_dp)]
        if self.prefix_rows is not None:
            # fresh (empty) per-row indexes: cached prefixes don't survive
            # a pool re-layout; re-use rebuilds them as traffic re-commits
            self.prefix_rows = [PrefixIndex(bs, self.kv.allocators[r])
                                for r in range(new_dp)]
            self.kv.prefix_indices = list(self.prefix_rows)
            self._attach_prefix_observers()
        # ---------------- re-pour the holders; re-route everyone else
        rep = self.replica if self.replica is not None else 0
        plan = []
        for q in self.queue:
            q.inflight_keys = []
            if q.rid not in placement:
                q.row = None               # re-route under the new geometry
        for r in holders:
            row, slot, need = placement[r.rid]
            r.row, r.slot = row, slot
            r.pc_blocks, r.pc_parent = 0, None
            self.slot_req[slot] = r
            ok = self.kv.ensure(slot, need * bs)
            assert ok, "planned placement must allocate"
            self.lens[slot] = r.prefilled
            dst = [int(self.kv.global_block(row, b))
                   for b in self.kv.seq_blocks(slot)]
            ex = exports[r.rid]
            self.write_blocks(dst, ex["payload"])
            plan.append(build_transfer_plan(ex, dst, rep, rep))
        self._refresh_block_tables()
        self.obs.inc("reshards_total")
        self.obs.inc("reshard_blocks_moved_total", blocks_moved)
        self.obs.emit("reshard_end", step=self.step_count,
                      old=f"{delta.old}", new=f"{delta.new}",
                      delta_kind=delta.kind, requests=len(holders),
                      blocks=blocks_moved, dropped_pins=dropped_pins)
        return ReshardReport(delta, len(holders), blocks_moved,
                             dropped_pins, tuple(plan))

    # ------------------------------------------------- serving facade (API)
    # ShiftEngine implements repro.engine.api.ServingClient; everything a
    # caller outside src/repro/engine/ needs goes through these methods
    # (plus obs/drain/snapshot) — never through engine private state.
    def submit(self, req: Request) -> int:
        """ServingClient entry: enqueue ``req``, return its rid."""
        self.add_request(req)
        return req.rid

    def stream(self, rid: int) -> List[int]:
        """Tokens generated so far for ``rid`` (a snapshot; exactly-once
        incremental delivery is the caller's DeliveryLog's job). Empty for
        unknown rids."""
        req = self._requests.get(rid)
        return list(req.generated) if req is not None else []

    def request(self, rid: int) -> Optional[Request]:
        """Read-only access to a submitted request's state (the Router's
        DeliveryLog polls these)."""
        return self._requests.get(rid)

    def set_replica(self, replica: Optional[int]):
        """Stamp this engine as cluster replica ``replica``: the id rides
        on every step record and lifecycle event it emits from now on, so
        one merged obs dump covers the whole cluster."""
        self.replica = replica
        self.obs.replica = replica

    def retained_snapshots(self) -> List[dict]:
        """The auto-snapshot ring (newest last) — what ``recover()``
        restores from; exposed for crash drills and external checkpoint
        shipping."""
        return list(self._snap_ring)

    def prefix_probe(self, tokens: List[int]) -> int:
        """Longest indexed prefix of ``tokens`` on this engine, in tokens,
        across all dp rows — WITHOUT the LRU bump (``match(bump=False)``),
        so cluster routing probes don't skew eviction recency. 0 when
        prefix caching is off."""
        if self.prefix_rows is None or len(tokens) < 2:
            return 0
        best = 0
        for idx in self.prefix_rows:
            m = idx.match(tokens, max_tokens=len(tokens) - 1, bump=False)
            best = max(best, len(m))
        return best * self.cfg.block_size

    def _queued_block_demand(self) -> int:
        """Blocks the unadmitted queue will need (the router load signal —
        same pricing as ``_route``'s pending-demand term)."""
        return sum(blocks_for_tokens(q.total_tokens + 1, self.cfg.block_size)
                   for q in self.queue if q.slot is None)

    def stats(self) -> EngineStats:
        """ServingClient stats: one frozen snapshot of the engine's serving
        state (queue/active/config counts/blocks/prefix), taken at a step
        boundary."""
        return EngineStats(
            steps=self.step_count,
            queue_depth=sum(1 for q in self.queue if q.slot is None),
            active=len(self.active),
            preemptions=self.preemptions,
            config_counts=self.config_counts,
            paged=self.paged,
            paged_disabled_reason=self.paged_disabled_reason,
            dp=self.dp,
            block_size=self.cfg.block_size,
            blocks_per_row=self.kv.num_blocks_per_row if self.paged else 0,
            free_blocks=self.kv.num_free_blocks if self.paged else 0,
            queued_block_demand=self._queued_block_demand(),
            prefix=self.prefix_stats,
            blocks=self.block_accounting(),
            replica=self.replica)

    # -------------------------------------------- live KV migration (cluster)
    # Block-granular request migration between replicas: extract (read-only)
    # -> admit on the destination -> write the block payload -> release on
    # the source (decrement-not-free). The Router drives the sequence and
    # only releases after the destination holds the data, so a failed
    # migration aborts with the source untouched.
    def migratable(self) -> List[int]:
        """Rids of requests a Router may migrate off this engine right now:
        active, prefill-complete, mid-decode. Requests inside a
        retry-backoff window are included — their remaining backoff is
        exported step-relative and re-based onto the destination's step
        clock, so migrating one neither extends nor shortens its penalty.
        Ordered least-recently-batched first (the cheapest to move: their
        streams are coldest)."""
        if not self.paged:
            return []
        return [r.rid for r in sorted(self.active,
                                      key=lambda r: (r.last_used, r.rid))
                if self._prefill_done(r) and not r.done]

    def extract_request(self, rid: int) -> Optional[dict]:
        """Read-only export of a live request for migration: its state dict
        plus the committed KV block payload (host numpy, gathered from the
        pool after flushing pending COW copies so the bytes are final).
        Returns None when ``rid`` is not currently migratable. Source
        state is NOT touched — release happens in ``release_migrated``
        after the destination holds the data."""
        req = self._requests.get(rid)
        if req is None or req.slot is None or not self.paged \
                or not self._prefill_done(req) or req.done:
            return None
        self._apply_copies()            # pending COW lands before the read
        row = self.kv.row_of(req.slot)
        local = self.kv.seq_blocks(req.slot)
        gids = np.asarray([self.kv.global_block(row, b) for b in local],
                          np.int32)

        def take(pool):
            arr = np.asarray(pool)
            return arr[:, gids].copy() if arr.ndim == 5 else arr[gids].copy()

        state = {"rid": req.rid, "prompt": list(req.prompt),
                 "generated": list(req.generated),
                 "max_new_tokens": req.max_new_tokens,
                 "arrival": req.arrival, "deadline": req.deadline,
                 "prefilled": req.prefilled,
                 "cached_tokens": req.cached_tokens,
                 "first_token_time": req.first_token_time,
                 "num_preemptions": req.num_preemptions,
                 "fail_count": req.fail_count, "retry_at": req.retry_at,
                 # backoff travels step-relative: destination step clocks
                 # are unrelated to the source's
                 "retry_remaining": max(0, req.retry_at - self.step_count)}
        return {"state": state, "n_blocks": len(local),
                "block_size": self.cfg.block_size,
                "src_blocks": [int(g) for g in gids],
                "payload": jax.tree.map(take, self.cache)}

    def admit_migrated(self, state: dict, n_blocks: int) -> Optional[list]:
        """Allocate ``n_blocks`` fresh blocks and register the migrated
        request on this engine (``assign_prefix``-style block mapping into
        a free slot of the least-loaded row). Returns the pool-global
        destination block ids to write the payload into, or None when no
        row has a free slot plus capacity (the migration aborts; the
        source was never touched)."""
        if not self.paged or state["rid"] in self._requests:
            return None
        need_tokens = n_blocks * self.cfg.block_size
        spr = self.slots_per_row
        for row in sorted(range(self.dp),
                          key=lambda r: (-self.kv.row_free_blocks(r), r)):
            slot = next((s for s in range(row * spr, (row + 1) * spr)
                         if self.slot_req[s] is None), None)
            if slot is None:
                continue
            if not self.kv.can_allocate(need_tokens, cached_blocks=[],
                                        row=row):
                continue
            if not self.kv.ensure(slot, need_tokens):
                continue
            req = Request(state["rid"], list(state["prompt"]),
                          max_new_tokens=state["max_new_tokens"],
                          arrival=state["arrival"],
                          deadline=state["deadline"])
            req.generated = list(state["generated"])
            req.prefilled = state["prefilled"]
            req.cached_tokens = state["cached_tokens"]
            req.first_token_time = state["first_token_time"]
            req.num_preemptions = state["num_preemptions"]
            req.fail_count = state["fail_count"]
            # re-base a mid-backoff request onto this engine's step clock
            # (older export dicts without the relative field keep the raw
            # retry_at — harmless, it only ever shortens the wait)
            if "retry_remaining" in state:
                req.retry_at = self.step_count + state["retry_remaining"]
            else:
                req.retry_at = state["retry_at"]
            req.row, req.slot = row, slot
            req.last_used = self.step_count
            self.slot_req[slot] = req
            self.lens[slot] = req.prefilled
            self.queue.append(req)
            self._requests[req.rid] = req
            self.obs.inc("migration_blocks_total", n_blocks)
            self.obs.emit("migrate_in", step=self.step_count, rid=req.rid,
                          row=row, slot=slot, blocks=n_blocks,
                          tokens=req.prefilled)
            local = self.kv.seq_blocks(slot)
            return [int(self.kv.global_block(row, b)) for b in local]
        return None

    def write_blocks(self, gids: list, payload):
        """Migration data plane: scatter ``payload`` (per-leaf
        ``[n_blocks, ...]`` arrays from ``extract_request``) into this
        engine's pool at pool-global ids ``gids``."""
        dst = jnp.asarray(np.asarray(gids, np.int32))

        def put(pool, data):
            d = jnp.asarray(data, dtype=pool.dtype)
            if pool.ndim == 5:
                return pool.at[:, dst].set(d)
            return pool.at[dst].set(d)

        self.cache = jax.tree.map(put, self.cache, payload)

    def release_migrated(self, rid: int):
        """Drop a migrated-away request from this engine: slot and blocks
        are released through ``free_seq`` (decrement-not-free — blocks a
        prefix index pins survive), the rid leaves the facade registry,
        and NO terminal FinishReason is recorded (the request lives on at
        the destination; ``migrate_out`` is the lifecycle event)."""
        req = self._requests.pop(rid, None)
        if req is None:
            return
        n_out = len(req.generated)
        row = req.row
        if req.slot is not None:
            self._release_slot(req)
        self.queue = [q for q in self.queue if q.rid != rid]
        self.obs.inc("requests_migrated_total")
        self.obs.emit("migrate_out", step=self.step_count, rid=rid,
                      row=row, n_out=n_out)
