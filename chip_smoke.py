#!/usr/bin/env python3
"""Chip smoke test: the served path once on a TPU, at published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the 2x2 base/shift phase, nothing else

One chip runs qwen3-8b at its published widths in bfloat16 with the depth
cut to 18 of its 36 layers (one chip's share of a two-stage pipeline: the
whole model does not fit one 16 GB v5e):

1. the production paged-attention kernel (native Pallas) on a ragged mixed
   batch at qwen3-8b's attention widths, against the gather oracle;
2. one full mixed step through the model: the compiled program must hold
   the kernel (``tpu_custom_call``) and its logits must be finite; on a
   depth cut that shares the weights, the step's logits must agree with
   the same step on the gather oracle;
3. eight requests of 20 to 1500 prompt tokens through ``build_engine``
   (chunked prefill and decode share steps); every request must finish
   ``ok`` and no KV block may leak.

``--chips 4`` runs the whole model on a 1x2x2 mesh instead: the pool's
shard-invariance, a request mix that both configs serve, the base
(SP2xTP2) and shift (TP4) programs on the same step inputs, and the peak
memory of every device.

Why the step comparisons hold their limit on a depth cut: two correct
programs that round differently (kernel vs gather, TP2 vs TP4 partial
sums) drift apart layer by layer in a random-weight bf16 network (base vs
shift at published widths on the CPU: 7.1e-3 at 2 layers, 2.85e-2 at 8),
while a wrong block, mask, head or shard already shows in the first
layers. The full-depth gap is printed for the record.

One process, no children. Every check runs and prints its numbers; any
failed check, or any error, makes the script exit non-zero. Without a
TPU, or outside a checkout of this repository, it fails before printing
a result. Wall times are informational, not metrics. The last line of
standard output is one JSON object naming the device."""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-8b"
ONE_CHIP_LAYERS = 18
CUT_LAYERS = 2          # depth of the step comparisons (see above)
# kernel and logit checks run bf16 against an f32 oracle on the same
# (bf16-rounded) inputs; 2e-2 is a few bf16 ulps (2^-8 relative) of the
# O(1) values compared, far below what a wrong mask, block or head gives
TOL = 2e-2


def require_tpu(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, "
                         f"have {len(devs)}")
    print(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs


class CompileClock:
    """Sums XLA backend compile time (persistent-cache reads included)
    from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


class Checks:
    """Failed checks, kept so that one run prints every number; ``done``
    raises if any failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, what):
        if not ok:
            print(f"FAILED: {what}")
            self.failed.append(what)

    def done(self):
        if self.failed:
            raise SystemExit(f"{len(self.failed)} check(s) failed: "
                             + "; ".join(self.failed))


def peak_bytes(devs):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devs]


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def check_kernel(check, *, Hkv=8, g=4, D=128, bs=16, C=64,
                 dtype=jnp.bfloat16, kcfg=None, seed=0):
    """Ragged mixed batch through ``ops.paged_ragged_attention``: decode
    rows, 64-token chunks, contexts over many blocks and one empty row,
    scattered over a head-major pool. Returns the largest error of a real
    column against the f32 gather oracle."""
    from repro.kernels import ops
    from repro.kernels.ops import KernelConfig
    ctx = np.array([1, 700, 1537, 64, 1000, 2048, 0, 333], np.int32)
    ql = np.array([1, 1, 1, 64, 64, 64, 0, 37], np.int32)
    B, Hq = len(ctx), Hkv * g
    nbs = [-(-int(c) // bs) for c in ctx]
    nmax, nblocks = max(nbs), sum(nbs) + 1
    rng = np.random.default_rng(seed)
    phys = rng.permutation(np.arange(1, nblocks))
    bt = np.zeros((B, nmax), np.int32)
    i = 0
    for b, nb in enumerate(nbs):
        bt[b, :nb] = phys[i:i + nb]
        i += nb
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, C, Hq, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (nblocks, Hkv, bs, D),
                           jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (nblocks, Hkv, bs, D),
                           jnp.float32).astype(dtype)
    args = (jnp.asarray(bt), jnp.asarray(ql), jnp.asarray(ctx))
    out = np.asarray(ops.paged_ragged_attention(q, kp, vp, *args, kcfg=kcfg),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ops.paged_ragged_attention(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), *args, kcfg=KernelConfig("gather")))
    check(bool(np.isfinite(out).all()), "kernel output is not finite")
    err = max(float(np.max(np.abs(out[b, :n] - want[b, :n])))
              for b, n in enumerate(ql) if n)
    print(f"kernel: B={B} C={C} Hkv={Hkv} g={g} D={D} bs={bs} "
          f"{jnp.dtype(dtype).name}, {nblocks} blocks: max |kernel - gather "
          f"oracle| = {err:.3e} (limit {TOL})")
    check(err <= TOL, f"kernel vs oracle error {err} > {TOL}")
    return err


def step_inputs(vocab, *, B=8, C=64, bs=16, nmax=8, seed=1):
    """One ragged mixed batch for a model step: decode rows over earlier
    context, prefill chunks, a partial chunk and an empty row. Returns the
    step's (tokens, q_lens, offsets, block_tables) and the pool size."""
    q_lens = np.array([1, 1, 64, 64, 17, 1, 40, 0], np.int32)[:B]
    offs = np.array([70, 5, 0, 64, 30, 120, 16, 0], np.int32)[:B]
    bt = (1 + np.arange(B * nmax, dtype=np.int32)).reshape(B, nmax)
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, vocab, (B, C), dtype=np.int32)
    return ((jnp.asarray(toks), jnp.asarray(q_lens), jnp.asarray(offs),
             jnp.asarray(bt)), B * nmax + 1, bs)


def depth_cut(model, params, n):
    """The first ``n`` layers of a stacked-body model and its parameters,
    sharing the weights (the layer stack is sliced, nothing is drawn)."""
    cfg = model.cfg
    assert not (cfg.prefix_layers or cfg.suffix_layers), cfg.name
    cut = dataclasses.replace(model,
                              cfg=dataclasses.replace(cfg, num_layers=n))
    n_body = n // len(cfg.layer_pattern)
    return cut, {**params, "body": jax.tree.map(lambda a: a[:n_body],
                                                params["body"])}


def step_logits(model, params, inputs, nblocks, bs, kcfg=None):
    """Last-token logits of one mixed step on a fresh pool, and the
    compiled program's text."""
    fn = jax.jit(model.forward_fn(sample=False, kernel=kcfg))
    pool = model.init_paged_cache(nblocks, bs)
    compiled = fn.lower(params, pool, *inputs).compile()
    logits, _ = compiled(params, pool, *inputs)
    return np.asarray(logits, np.float32), compiled.as_text()


def compare_steps(label, a, b, q_lens, check=None):
    """Relative logit gap over the rows that carry tokens; held to ``TOL``
    when a ``check`` is given, printed for the record otherwise."""
    real = np.asarray(q_lens) > 0
    la, lb = a[real], b[real]
    if not (np.isfinite(la).all() and np.isfinite(lb).all()):
        raise AssertionError(f"{label}: logits are not finite")
    gap = _gap(la, lb)
    print(f"{label}: logits {a.shape}, max |diff| / max |logit| = "
          f"{gap:.3e} " + (f"(limit {TOL})" if check else "(informational)"))
    if check:
        check(gap <= TOL, f"{label}: logit gap {gap} > {TOL}")
    return gap


def serve(eng, prompt_lens, max_new, check, *, seed=2):
    """Submit one request per prompt length, run to idle, drain; every
    request must finish ok with max_new tokens and no block may leak."""
    from repro.engine import Request
    from repro.engine.request import FinishReason
    vocab = eng.base.cfg.vocab_size
    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(1, vocab, n).tolist(),
                    max_new_tokens=max_new, arrival=time.monotonic())
            for i, n in enumerate(prompt_lens)]
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    eng.drain()
    wall = time.perf_counter() - t0
    for r in reqs:
        toks = eng.stream(r.rid)
        print(f"  req {r.rid}: prompt {len(r.prompt)}, {len(toks)} tokens, "
              f"reason={r.finish_reason}")
        check(r.finish_reason == FinishReason.OK and len(toks) == max_new,
              f"request {r.rid} ended {r.finish_reason} with {len(toks)} "
              "tokens")
        check(all(0 <= t < vocab for t in toks),
              f"request {r.rid} emitted an id outside the vocabulary")
    led = eng.block_accounting()
    st = eng.stats()
    print(f"  {sum(prompt_lens)} prompt + {len(reqs) * max_new} generated "
          f"tokens in {eng.step_count} steps, configs {st.config_counts}, "
          f"blocks after drain: used={led.used} pinned={led.pinned} "
          f"(wall {wall:.1f} s incl. compiles, informational)")
    check(not (led.used or led.pinned), f"leaked blocks: {led}")
    return st.config_counts


def one_chip(devs, clock, check):
    from repro.kernels.ops import KernelConfig
    from repro.launch.serve import build_engine
    check_kernel(check)
    t0 = time.perf_counter()
    eng = build_engine(ARCH, reduced=False, num_layers=ONE_CHIP_LAYERS,
                       slots=8, s_max=4096, chunk=64, block_size=16)
    cfg = eng.base.cfg
    pool_gb = sum(a.nbytes for a in jax.tree.leaves(eng.cache)) / 1e9
    par_gb = sum(a.nbytes for a in jax.tree.leaves(eng.p_base)) / 1e9
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}, "
          f"{cfg.num_layers} of 36 layers (depth cut), bf16: params "
          f"{par_gb:.2f} GB, KV pool {eng.kv.num_blocks_per_row} blocks = "
          f"{pool_gb:.2f} GB (built in {time.perf_counter() - t0:.1f} s)")
    inputs, nblocks, bs = step_inputs(cfg.vocab_size)
    logits, text = step_logits(eng.base, eng.p_base, inputs, nblocks, bs)
    check("tpu_custom_call" in text,
          "compiled mixed step holds no tpu_custom_call")
    check(bool(np.isfinite(logits).all()), "mixed step logits not finite")
    print(f"mixed step, {cfg.num_layers} layers: compiled program holds the "
          f"Pallas kernel: {'tpu_custom_call' in text}; logits "
          f"{logits.shape} finite: {bool(np.isfinite(logits).all())}")
    cut, p_cut = depth_cut(eng.base, eng.p_base, CUT_LAYERS)
    got, _ = step_logits(cut, p_cut, inputs, nblocks, bs)
    want, _ = step_logits(cut, p_cut, inputs, nblocks, bs,
                          KernelConfig("gather"))
    compare_steps(f"mixed step, {CUT_LAYERS}-layer cut, kernel vs gather "
                  "oracle", got, want, inputs[1], check)
    print("served run:")
    serve(eng, [20, 64, 150, 400, 700, 1000, 1300, 1500], 32, check)
    print(f"compiles: {clock.n}, {clock.seconds:.1f} s; peak_bytes_in_use: "
          f"{peak_bytes(devs[:1])}")


def four_chip(devs, clock, check):
    from repro.core.invariance import verify_paged_invariance
    from repro.launch.serve import build_engine
    t0 = time.perf_counter()
    eng = build_engine(ARCH, reduced=False, sp=2, tp=2, slots=8, s_max=1024,
                       chunk=64, block_size=16)
    base, shift = eng.base, eng.shift
    print(f"model: {base.cfg.name}, all {base.cfg.num_layers} layers, bf16; "
          f"base {base.lay.describe()}, shift {shift.lay.describe()} (built "
          f"in {time.perf_counter() - t0:.1f} s)")
    isp = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    want = jax.tree.leaves(base.shardings(base.paged_cache_specs()))
    check(all(a.sharding.is_equivalent_to(s, a.ndim)
              for a, s in zip(jax.tree.leaves(eng.cache), want)),
          "live pool is not in the base pool sharding")
    ok = verify_paged_invariance(
        jax.tree.leaves(eng.cache),
        jax.tree.leaves(base.paged_cache_specs(), is_leaf=isp),
        jax.tree.leaves(shift.paged_cache_specs(), is_leaf=isp),
        (eng.cfg.max_slots, eng.kv.max_blocks_per_seq),
        base.block_table_spec(), shift.block_table_spec(), base.mesh,
        base.lay.model_axes, dp_axes=base.lay.dp_axes)
    print(f"verify_paged_invariance on the live pool: {ok}")
    check(ok, "paged pool is not shard-invariant")
    print("served run:")
    counts = serve(eng, [40, 47, 53, 60], 8, check)
    check(counts["base"] > 0 and counts["shift"] > 0,
          f"both configs must serve: {counts}")
    inputs, nblocks, bs = step_inputs(base.cfg.vocab_size)
    lb, tb = step_logits(base, eng.p_base, inputs, nblocks, bs)
    ls, ts = step_logits(shift, eng.p_shift, inputs, nblocks, bs)
    for name, text in (("base", tb), ("shift", ts)):
        check("tpu_custom_call" in text, f"{name} step holds no "
              "tpu_custom_call")
    compare_steps(f"mixed step, {base.cfg.num_layers} layers, base vs shift",
                  lb, ls, inputs[1])
    (cb, pb), (cs, ps) = (depth_cut(base, eng.p_base, CUT_LAYERS),
                          depth_cut(shift, eng.p_shift, CUT_LAYERS))
    lb, _ = step_logits(cb, pb, inputs, nblocks, bs)
    ls, _ = step_logits(cs, ps, inputs, nblocks, bs)
    compare_steps(f"mixed step, {CUT_LAYERS}-layer cut, base vs shift", lb,
                  ls, inputs[1], check)
    peaks = peak_bytes(devs[:4])
    print(f"compiles: {clock.n}, {clock.seconds:.1f} s; peak_bytes_in_use "
          f"per device: {peaks}")
    check(peaks[0] <= 1.25 * max(peaks[1:]),
          f"device 0 holds more than its share: {peaks}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the 2x2 base/shift phase")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels.ops import KernelConfig
    from repro.launch.compile_cache import enable_compile_cache
    backend = KernelConfig().resolve()
    if backend != "pallas":
        raise SystemExit(f"paged attention resolves to {backend!r}, not the "
                         "native Pallas kernel")
    print(f"compile cache: {enable_compile_cache()}")
    clock, check = CompileClock(), Checks()
    (four_chip if args.chips == 4 else one_chip)(devs, clock, check)
    check.done()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
