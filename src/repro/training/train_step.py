"""Explicit-collective training step (Ulysses training — the origin of SP).

The whole step runs in one ``shard_map``: local loss -> jax.grad ->
(optionally int8-compressed) gradient all-reduce over (dp, sp) -> AdamW with
ZeRO-1 moment sharding over dp. Gradient accumulation over microbatches
keeps activation memory bounded; remat is applied per layer superblock."""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.models import Model
from repro.models import transformer as T
from .optimizer import AdamWConfig, adamw_init, adamw_update, opt_state_specs
from .compress import int8_compress_psum


@dataclass
class Trainer:
    model: Model
    opt: AdamWConfig = field(default_factory=AdamWConfig)
    microbatch: int = 0          # 0 = no accumulation
    grad_compression: str = "none"   # "none" | "int8"
    remat: bool = True

    # ------------------------------------------------------------------
    def init_opt_state(self, params):
        st = adamw_init(params, self.opt)
        if self.grad_compression == "int8":
            st["err"] = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return st

    def opt_specs(self, abstract_params):
        lay = self.model.lay
        pspecs = self.model.param_specs()
        mv = opt_state_specs(pspecs, abstract_params, lay)
        st = {"m": jax.tree.map(lambda d: d["m"], mv,
                                is_leaf=lambda x: isinstance(x, dict) and "m" in x),
              "v": jax.tree.map(lambda d: d["v"], mv,
                                is_leaf=lambda x: isinstance(x, dict) and "m" in x),
              "step": P()}
        if self.grad_compression == "int8":
            st["err"] = pspecs
        return st

    # ------------------------------------------------------------------
    def train_step_fn(self):
        model = self.model
        cfg, lay, pod = model.cfg, model.lay, model.pod_scale
        opt_cfg = self.opt
        micro = self.microbatch
        compress = self.grad_compression == "int8"
        remat = self.remat

        pspec = model.param_specs()
        reduce_axes = tuple(lay.dp_axes) + tuple(lay.sp_axes)
        shard_axes = tuple(lay.tp_axes)  # disjoint param shards

        def local_loss(params, tokens, labels, fe, ef):
            # token-local mean; grad reduction over (dp, sp) happens manually
            lay_local = lay
            return T.loss_body(params, tokens, labels, cfg, lay_local, pod,
                               fe, ef, remat=remat)

        def body(params, opt_state, tokens, labels, *rest):
            fe = rest[0] if cfg.frontend == "vision_stub" else None
            ef = rest[-1] if cfg.encoder_layers else None

            if micro and micro > 1:
                bs = tokens.shape[0] // micro

                def acc_step(carry, xs):
                    g_acc, l_acc = carry
                    tk, lb, fe_m, ef_m = xs
                    l, g = jax.value_and_grad(local_loss)(params, tk, lb,
                                                          fe_m, ef_m)
                    return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params)
                tk = tokens.reshape(micro, bs, *tokens.shape[1:])
                lb = labels.reshape(micro, bs, *labels.shape[1:])
                fe_s = (fe.reshape(micro, bs, *fe.shape[1:]) if fe is not None
                        else jnp.zeros((micro, 1)))
                ef_s = (ef.reshape(micro, bs, *ef.shape[1:]) if ef is not None
                        else jnp.zeros((micro, 1)))

                def acc_step2(carry, xs):
                    tk_, lb_, fe_, ef_ = xs
                    return acc_step(carry, (
                        tk_, lb_, fe_ if fe is not None else None,
                        ef_ if ef is not None else None))

                (grads, loss), _ = jax.lax.scan(
                    acc_step2, (g0, 0.0), (tk, lb, fe_s, ef_s))
                grads = jax.tree.map(lambda g: g / micro, grads)
                loss = loss / micro
            else:
                loss, grads = jax.value_and_grad(local_loss)(
                    params, tokens, labels, fe, ef)

            # ---- gradient reduction over (dp, sp): loss_body already psums
            # the loss mean over (dp, sp); its AD transposes token sharding
            # into correct *local* parameter grads, so the cross-replica sum
            # here completes the data-parallel reduction.
            if compress:
                err = opt_state["err"]
                gp = jax.tree.map(
                    lambda g, e: int8_compress_psum(g, e, reduce_axes),
                    grads, err)
                grads = jax.tree.map(lambda t: t[0], gp,
                                     is_leaf=lambda x: isinstance(x, tuple))
                new_err = jax.tree.map(lambda t: t[1], gp,
                                       is_leaf=lambda x: isinstance(x, tuple))
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g.astype(jnp.float32), reduce_axes)
                    if reduce_axes else g.astype(jnp.float32), grads)
                new_err = None

            new_p, new_m, new_v, step = adamw_update(
                params, grads, opt_state["m"], opt_state["v"],
                opt_state["step"], opt_cfg, lay, param_specs=pspec,
                tp_shard_axes=shard_axes)
            new_state = {"m": new_m, "v": new_v, "step": step}
            if compress:
                new_state["err"] = new_err
            return new_p, new_state, loss

        return body

    def wrapped(self, opt_specs):
        """shard_map-wrapped step for a mesh deployment."""
        model = self.model
        cfg, lay = model.cfg, model.lay
        pspec = model.param_specs()
        dp = lay.dp_axes or None
        seq = lay.sp_axes or None
        args = [pspec, opt_specs, P(dp, seq), P(dp, seq)]
        if cfg.frontend == "vision_stub":
            args.append(P(dp, None, None))
        if cfg.encoder_layers:
            args.append(P(dp, seq, None))
        body = self.train_step_fn()
        if model.mesh is None:
            return body
        return shard_map(body, mesh=model.mesh, in_specs=tuple(args),
                         out_specs=(pspec, opt_specs, P()), check_vma=False)
