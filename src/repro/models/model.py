"""Public model API: a ``Model`` bundles (config, layout) and exposes
jit/shard_map-wrapped step functions plus abstract init for the dry-run."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.paged_ragged_attention import kv_step_blocks
from repro.parallel import Layout
from . import transformer as T

POD_SCALE_ARCHS = {"deepseek-v3-671b", "llama4-maverick-400b-a17b",
                   "llama4-17b-16e"}


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


@dataclass
class Model:
    cfg: object
    lay: Layout
    mesh: Optional[Mesh] = None
    dtype: object = jnp.bfloat16
    # paged-attention backend (repro.kernels.KernelConfig); None = the
    # dispatch default (Pallas on TPU, its bit-exact jnp mirror elsewhere).
    # Step-fn factories accept a per-call override (the engine threads
    # EngineConfig.kernel through it).
    kernel: Optional[object] = None

    @property
    def pod_scale(self) -> bool:
        return self.cfg.name in POD_SCALE_ARCHS

    # ------------------------------------------------------------ init
    def _placed(self, init, specs):
        """``init`` jitted so that every leaf is created directly in its
        sharding on the mesh (on the default device without one): no leaf
        of a sharded model is ever materialised whole on one device."""
        out = None if self.mesh is None else self.shardings(specs)
        return jax.jit(init, out_shardings=out)

    def init_params(self, key):
        cfg, lay, dtype, pod = self.cfg, self.lay, self.dtype, self.pod_scale
        return self._placed(lambda k: T.init_params(k, cfg, lay, dtype, pod),
                            self.param_specs())(key)

    def abstract_params(self):
        return jax.eval_shape(self.init_params, jax.random.key(0))

    def param_specs(self):
        return T.param_specs(self.cfg, self.lay, self.pod_scale)

    def init_cache(self, batch: int, s_max: int):
        return T.init_cache(self.cfg, self.lay, batch, s_max, self.dtype)

    def abstract_cache(self, batch: int, s_max: int):
        return jax.eval_shape(lambda: self.init_cache(batch, s_max))

    def cache_specs(self):
        return T.cache_specs(self.cfg, self.lay)

    # ------------------------------------------------------------ paged cache
    @property
    def supports_paged(self) -> bool:
        """True when every cached layer is plain GQA attention — the kinds
        whose [kv_slots, block_size, Dh] block layout is shard-invariant.
        MLA (latent layout), local (ring buffer), rglru/ssd (recurrent
        state) and encoder/decoder stacks keep the contiguous cache."""
        return (self.cfg.mla is None and not self.cfg.encoder_layers
                and all(k in ("attn", "moe") for k in self.cfg.layer_kinds))

    def init_paged_cache(self, num_blocks: int, block_size: int):
        """Block pools with ``num_blocks`` physical blocks PER dp row (the
        leading pool axis is ``dp * num_blocks``, sharded over the dp
        axes). With dp == 1 this is exactly the global pool size."""
        cfg, lay, dtype = self.cfg, self.lay, self.dtype
        return self._placed(
            lambda: T.init_paged_cache(cfg, lay, num_blocks, block_size,
                                       dtype),
            self.paged_cache_specs())()

    def abstract_paged_cache(self, num_blocks: int, block_size: int):
        return jax.eval_shape(
            lambda: self.init_paged_cache(num_blocks, block_size))

    def paged_cache_specs(self):
        return T.paged_cache_specs(self.cfg, self.lay)

    def block_table_spec(self):
        from .attention import block_table_spec
        return block_table_spec(self.lay)

    def attn_step_blocks(self, chunk: int, table_width: int,
                         block_size: int) -> int:
        """KV blocks per grid step of the paged attention kernel in a step
        launched with ``chunk`` query columns and block tables
        ``table_width`` wide: ``kv_step_blocks`` at the shapes each shard's
        kernel call sees (its own kv heads and their query groups; the
        chunk is whole after the sequence exchange)."""
        from .attention import get_plan
        plan = get_plan(self.cfg, self.lay)
        g = plan.q_per_rank // plan.kv_per_rank
        return kv_step_blocks(bs=block_size, nmax=table_width,
                              rows=g * chunk, head_dim=self.cfg.head_dim,
                              kv_heads=plan.kv_per_rank, dtype=self.dtype)

    # ---------------------------------------------------------- step fns
    # All bodies are closed over (cfg, lay) and run inside shard_map when a
    # mesh is present; on a single device they run as plain functions (all
    # collectives no-op because the layout has no axes).

    def _wrap(self, body, in_specs, out_specs):
        if self.mesh is None:
            return body
        return shard_map(body, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _io_specs(self):
        lay = self.lay
        dp = lay.dp_axes or None
        seq = lay.sp_axes or None
        tok_b = tuple(lay.dp_axes) + tuple(lay.sp_axes)  # decode batch axes
        return dp, seq, (tok_b or None)

    def prefill_fn(self, paged: bool = False, kernel=None):
        """With ``paged=True`` the returned fn takes an extra
        ``block_tables`` [B, nmax] arg after ``offsets`` and the cache arg
        is the paged block pool (same sharded bytes in base and shift).
        ``kernel`` overrides the model's paged-attention KernelConfig."""
        cfg, lay, pod = self.cfg, self.lay, self.pod_scale
        kcfg = kernel or self.kernel
        dp, seq, _ = self._io_specs()
        pspec = self.param_specs()
        cspec = self.paged_cache_specs() if paged else self.cache_specs()

        args = [pspec, cspec, P(dp, seq), P(dp)]
        if paged:
            args.append(self.block_table_spec())
        extras = []
        if cfg.frontend == "vision_stub":
            extras.append(P(dp, None, None))
        if cfg.encoder_layers:
            extras.append(P(dp, seq, None))

        def body(params, cache, tokens, offsets, *rest):
            bt = None
            if paged:
                bt, rest = rest[0], rest[1:]
            fe = rest[0] if cfg.frontend == "vision_stub" else None
            ef = rest[-1] if cfg.encoder_layers else None
            logits, cache = T.prefill_body(params, cache, tokens, offsets,
                                           cfg, lay, pod, fe, ef,
                                           block_tables=bt, kcfg=kcfg)
            return logits, cache

        out = (P(dp, lay.tp_axes or None), cspec)
        return self._wrap(body, tuple(args + extras), out)

    def decode_fn(self, sample: bool = True, paged: bool = False,
                  kernel=None):
        cfg, lay, pod = self.cfg, self.lay, self.pod_scale
        kcfg = kernel or self.kernel
        dp, _, tok_b = self._io_specs()
        pspec = self.param_specs()
        cspec = self.paged_cache_specs() if paged else self.cache_specs()

        def body(params, cache, tokens, lens, *rest):
            bt = rest[0] if paged else None
            logits, cache = T.decode_body(params, cache, tokens, lens, cfg,
                                          lay, pod, block_tables=bt,
                                          kcfg=kcfg)
            if sample:
                return T.greedy_body(logits, lay), cache
            return logits, cache

        in_specs = [pspec, cspec, P(tok_b), P(dp)]
        if paged:
            in_specs.append(self.block_table_spec())
        out_tok = P(dp) if sample else P(tok_b, lay.tp_axes or None)
        return self._wrap(body, tuple(in_specs), (out_tok, cspec))

    def forward_fn(self, paged: bool = True, sample: bool = True,
                   kernel=None, n_last: int = 1):
        """Unified mixed-batch step: chunked-prefill rows (q_len up to the
        chunk width) and decode rows (q_len == 1) in ONE forward pass over
        the shared paged pool. For the paged engine this replaces the
        separate prefill/decode program pair — the shift policy sees the
        combined token count and the device batch is compacted to active
        rows. Signature of the returned fn:
        ``(params, pool, tokens [B, C], q_lens [B], offsets [B],
        block_tables [B, nmax], *extras) -> (next_tokens [B], pool)``.

        ``n_last`` > 1 is the speculative verify width: the ragged
        extraction returns the last n_last query positions per row
        (next_tokens [B, n_last]); n_last == 1 compiles the exact
        original single-token program."""
        if not paged:
            raise ValueError("the mixed forward requires the paged KV cache")
        cfg, lay, pod = self.cfg, self.lay, self.pod_scale
        kcfg = kernel or self.kernel
        dp, seq, _ = self._io_specs()
        pspec = self.param_specs()
        cspec = self.paged_cache_specs()

        args = [pspec, cspec, P(dp, seq), P(dp), P(dp),
                self.block_table_spec()]
        extras = []
        if cfg.frontend == "vision_stub":
            extras.append(P(dp, None, None))

        def body(params, cache, tokens, q_lens, offsets, bt, *rest):
            fe = rest[0] if cfg.frontend == "vision_stub" else None
            return T.mixed_body(params, cache, tokens, q_lens, offsets, cfg,
                                lay, pod, fe, block_tables=bt, sample=sample,
                                kcfg=kcfg, n_last=n_last)

        if n_last > 1:
            out_tok = P(dp, None) if sample else P(dp, None,
                                                   lay.tp_axes or None)
        else:
            out_tok = P(dp) if sample else P(dp, lay.tp_axes or None)
        return self._wrap(body, tuple(args + extras), (out_tok, cspec))

    def loss_fn(self, remat: bool = True):
        cfg, lay, pod = self.cfg, self.lay, self.pod_scale
        dp, seq, _ = self._io_specs()
        pspec = self.param_specs()
        args = [pspec, P(dp, seq), P(dp, seq)]
        if cfg.frontend == "vision_stub":
            args.append(P(dp, None, None))
        if cfg.encoder_layers:
            args.append(P(dp, seq, None))

        def body(params, tokens, labels, *rest):
            fe = rest[0] if cfg.frontend == "vision_stub" else None
            ef = rest[-1] if cfg.encoder_layers else None
            return T.loss_body(params, tokens, labels, cfg, lay, pod, fe, ef,
                               remat=remat)

        return self._wrap(body, tuple(args), P())

    # ------------------------------------------------------------ shardings
    def shardings(self, spec_tree):
        assert self.mesh is not None
        return _named(self.mesh, spec_tree)


def build_model(cfg, mesh: Optional[Mesh] = None, *, sp=(), tp=(), dp=(),
                dtype=jnp.bfloat16) -> Model:
    if mesh is None:
        lay = Layout()
    else:
        lay = Layout.from_mesh(mesh, dp=dp, sp=sp, tp=tp)
    return Model(cfg=cfg, lay=lay, mesh=mesh, dtype=dtype)
