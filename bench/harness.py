"""One cell of the served-path benchmark, driven end to end.

A cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); ``bench/cells/<cell>.json`` holds what
belongs to the pair alone (the correctness limit). The harness:

1. builds the served path (``repro`` models, ``ShiftEngine``) with weights
   drawn on the device from the seed (``bench.weights``);
2. warms up: serves a seed-derived warm-up stream of the cell's own
   traffic (a backlog: fills the slots), then compiles every shape bucket
   next to the ones it reached;
3. serves the window, stamping each token on the host clock after the
   ``step()`` that delivered it (``step()`` returns once the device is
   done: it reads the sampled tokens back);
4. drains until every request sent in the window has its first token;
5. reads the device's peak memory, frees the served path, and compares a
   seed-drawn sample of the served tokens with the float32 reference.

The harness takes from the program only the system under test and its
spans, counters and kernel names."""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench import workload as WL

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
clock = time.monotonic


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    entry: dict            # the BENCHMARK.json workloads entry
    spec: dict             # configuration file
    mix: dict              # traffic file
    local: dict            # bench/cells/<cell>.json
    e2e: List[dict]        # end-to-end metrics this cell reports
    layer: List[dict]      # per-layer metrics this cell reports
    chips: int


def _json(path):
    with open(path) as f:
        return json.load(f)


def _reports(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


def load_cell(name, root=ROOT):
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bdir = os.path.join(root, "bench")
    return Cell(
        name=name, entry=entry, spec=_json(os.path.join(root, cfg["file"])),
        mix=WL.load_mix(WL.mix_path(bdir, entry["traffic"])),
        local=_json(os.path.join(bdir, "cells", f"{name}.json")),
        e2e=[m for m in bench["end_to_end"] if _reports(m, name)],
        layer=[m for m in bench["per_layer"] if _reports(m, name)],
        chips=int(entry["chips"]))


# ------------------------------------------------------------ compile clock
class CompileClock:
    """Counts executables built (compiled, or read from the persistent
    cache) from JAX's own monitoring event. Copied from ``chip_smoke.py``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


# ------------------------------------------------------------------ build
def s_max_for(mix, block_size):
    n = WL.max_tokens(mix)
    return -(-n // block_size) * block_size


def _check_config(mc, spec):
    """The program's registered configuration must be the one the file
    states (and the reference runs)."""
    want = {"d_model": spec["hidden_size"],
            "num_heads": spec["num_attention_heads"],
            "num_kv_heads": spec["num_key_value_heads"],
            "head_dim": W.dims(spec)["D"], "d_ff": spec["intermediate_size"],
            "vocab_size": spec["vocab_size"],
            "num_layers": spec["num_hidden_layers"],
            "qkv_bias": bool(spec.get("qkv_bias")),
            "qk_norm": bool(spec.get("qk_norm")),
            "rope_theta": float(spec["rope_theta"]),
            "norm_eps": float(spec["rms_norm_eps"]),
            "tie_embeddings": bool(W.served(spec, "tie_word_embeddings"))}
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if bad or mc.layer_pattern != ("attn",) or mc.prefix_layers \
            or mc.suffix_layers or mc.act != "silu" or mc.norm != "rmsnorm":
        raise SystemExit(f"program config {mc.name} differs from the "
                         f"configuration file: {bad}")


def _same_tree(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(x.shape == y.shape and x.dtype == y.dtype
                            for x, y in zip(la, lb))


def _bytes_on_device0(tree):
    return sum(a.addressable_shards[0].data.nbytes
               for a in jax.tree.leaves(tree))


def build(cell: Cell, seed: int, devices):
    """The served path for ``cell`` with the seed's weights. Returns the
    engine and a description of what was built."""
    from repro.configs import get_config
    from repro.core.policy import ThresholdPolicy
    from repro.engine import EngineConfig, ShiftEngine
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.parallel import Layout
    spec, mix = cell.spec, cell.mix
    mesh_cfg, eng_cfg = spec["mesh"], mix["engine"]
    mc = get_config(spec["arch"])
    if spec["num_hidden_layers"] != mc.num_layers:
        mc = dataclasses.replace(mc, num_layers=spec["num_hidden_layers"])
    _check_config(mc, spec)
    dtype = jnp.dtype(spec["dtype"])
    sp, tp = int(mesh_cfg["sp"]), int(mesh_cfg["tp"])
    if sp * tp == 1:
        base = Model(cfg=mc, lay=Layout(), mesh=None, dtype=dtype)
        shift = base
    else:
        mesh = make_test_mesh(data=1, sp=sp, tp=tp)
        lay = Layout.from_mesh(mesh, dp=("data",), sp=("sp",), tp=("tp",))
        base = Model(cfg=mc, lay=lay, mesh=mesh, dtype=dtype)
        shift = Model(cfg=mc, lay=lay.to_shift(), mesh=mesh, dtype=dtype)
    groups = max(base.lay.G, 1)
    root = W.root_key(seed)
    draw = lambda k: W.program_tree(spec, k, groups, dtype)  # noqa: E731
    for m in {id(base): base, id(shift): shift}.values():
        if not _same_tree(jax.eval_shape(draw, root),
                          jax.eval_shape(m.init_params, jax.random.key(0))):
            raise SystemExit("the program's parameter tree is not the "
                             "layout bench/weights.py draws")
    out = None if base.mesh is None else base.shardings(base.param_specs())
    p_base = jax.jit(draw, out_shardings=out)(root)
    p_shift = (p_base if shift is base else
               jax.device_put(p_base, shift.shardings(shift.param_specs())))
    bs = int(eng_cfg["block_size"])
    s_max = s_max_for(mix, bs)
    nmax = -(-s_max // bs)
    slots = int(eng_cfg["slots"])
    params_b = _bytes_on_device0(p_base) + (
        0 if p_shift is p_base else _bytes_on_device0(p_shift))
    m = W.dims(spec)
    kv_heads_dev = max(m["K"] // groups, 1)
    block_b = m["L"] * 2 * kv_heads_dev * bs * m["D"] * dtype.itemsize
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    pool = spec["pool"]
    num_blocks = slots * nmax + 1
    if limit:
        # the step holds the pool and a copy of it (the layer scan carries
        # the stacked pool), so the pool gets its share of what parameters
        # and headroom leave
        num_blocks = min(num_blocks, int(
            (limit - params_b - float(pool["headroom_bytes"]))
            // (pool["copies"] * block_b)))
    if num_blocks <= nmax + 1:
        raise SystemExit(f"the pool holds {num_blocks} blocks, less than "
                         f"one longest request ({nmax})")
    threshold = int(spec["engine"]["threshold"])
    ecfg = EngineConfig(max_slots=slots, s_max=s_max,
                        prefill_chunk=int(eng_cfg["chunk"]),
                        threshold=threshold, block_size=bs,
                        num_blocks=num_blocks)
    eng = ShiftEngine(base, shift, p_base, p_shift, ecfg,
                      policy=ThresholdPolicy(threshold))
    if not (eng.paged and eng.mixed):
        raise SystemExit("the served path did not take the paged mixed step")
    jax.block_until_ready(eng.cache)
    info = {"num_blocks": num_blocks, "block_bytes_per_device": block_b,
            "pool_bytes_per_device": num_blocks * block_b,
            "params_bytes_per_device": params_b, "s_max": s_max,
            "nmax": nmax, "slots": slots, "bytes_limit": limit,
            "base": base.lay.describe(), "shift": shift.lay.describe()}
    return eng, info


# ---------------------------------------------------------------- shapes
def _pow2(n):
    return 1 << max(int(n) - 1, 0).bit_length()


class Shapes:
    """Wraps the engine's step programs to record each launch's shape
    bucket (config, rows, chunk, table width); the call is unchanged."""

    def __init__(self, eng):
        self.eng = eng
        self.seen = set()
        self.launches = []
        self.orig = dict(eng.deploy.forward)
        for mode, fn in self.orig.items():
            eng.deploy.forward[mode] = self._wrap(mode, fn)

    def _wrap(self, mode, fn):
        def call(params, cache, toks, *rest):
            shape = (mode, toks.shape[0], toks.shape[1], rest[2].shape[1])
            self.seen.add(shape)
            self.launches.append(shape)
            return fn(params, cache, toks, *rest)
        return call

    def closure(self, chunk, nmax, slots, recent=1.0):
        """The buckets next to those reached, per config: row buckets from
        the smallest reached to twice the largest (up to the slots), table
        buckets from the smallest reached up to the cap, and every chunk
        width reached. ``recent`` < 1 looks only at that last share of the
        launches (a backlog's fill ramps through buckets its window never
        uses)."""
        out = set()
        tail = set(self.launches[int(len(self.launches) * (1 - recent)):])
        p2 = [1 << i for i in range(16)]
        for mode in {s[0] for s in tail}:
            seen = [s for s in tail if s[0] == mode]
            rs = [s[1] for s in seen]
            ns = [s[3] for s in seen]
            cs = {s[2] for s in seen if s[2] <= chunk}
            rows = {r for r in p2
                    if min(rs) <= r <= min(2 * max(rs), _pow2(slots))}
            tabs = {n for n in p2 if min(ns) <= n < nmax} | {nmax}
            out |= {(mode, r, c, n) for r in rows for c in cs for n in tabs}
        return out

    def warm(self, shapes):
        """Compile and run each bucket once on padding rows (no token, the
        null block), which touch no request's state."""
        eng = self.eng
        for mode, R, C, N in sorted(shapes - self.seen):
            params = eng.p_base if mode == "base" else eng.p_shift
            args = [jnp.zeros((R, C), jnp.int32), jnp.zeros((R,), jnp.int32),
                    jnp.zeros((R,), jnp.int32), jnp.zeros((R, N), jnp.int32)]
            nxt, eng.cache = self.orig[mode](params, eng.cache, *args)
            np.asarray(nxt)
            self.seen.add((mode, R, C, N))


# ---------------------------------------------------------------- serving
@dataclasses.dataclass
class Tracked:
    req: object
    due: float
    phase: str
    sent: float
    n: int = 0                        # tokens seen so far
    p: int = 0                        # prefilled tokens seen so far
    times: List[float] = dataclasses.field(default_factory=list)
    by_config: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"base": 0, "shift": 0})


class Driver:
    """Submits requests and steps the engine, stamping every delivered
    token on the host clock after the ``step()`` that produced it."""

    def __init__(self, eng, vocab, seed, annotate=False):
        self.eng = eng
        self.vocab, self.seed = vocab, seed
        self.live: Dict[int, Tracked] = {}
        self.all: Dict[int, Tracked] = {}
        self.steps: List[tuple] = []  # launched steps (see step())
        self.lateness: List[float] = []
        self.next_rid = 0
        self.annotate = annotate

    def submit(self, item, due):
        from repro.engine import Request
        now = clock()
        r = Request(self.next_rid, item.prompt, max_new_tokens=item.out_len,
                    arrival=due)
        self.next_rid += 1
        if self.annotate:
            with jax.profiler.TraceAnnotation("bench_submit"):
                self.eng.submit(r)
        else:
            self.eng.submit(r)
        t = Tracked(r, due, item.phase, now)
        self.live[r.rid] = self.all[r.rid] = t
        self.lateness.append(now - due)
        return t

    def busy(self):
        return bool(self.eng.queue)

    def step(self):
        """One engine step. A launched step is recorded as (start, end,
        config, rows, prefill tokens, decode tokens, attention work,
        attended context) where attention work counts (query, key) pairs: a
        row of ``q`` fresh tokens after ``o`` cached ones attends
        ``q*o + q*(q+1)/2``; the attended context is the engine's
        ``attn_ctx_tokens`` (sum over rows of ``o + q``)."""
        t0 = clock()
        if self.annotate:
            with jax.profiler.StepTraceAnnotation(
                    "bench_step", step_num=len(self.steps)):
                progressed = self.eng.step()
        else:
            progressed = self.eng.step()
        t1 = clock()
        rec = self.eng.obs.step_records[-1]
        cfg = rec["config"]
        work = 0
        for rid, st in list(self.live.items()):
            r = st.req
            n = len(r.generated)
            if n > st.n:
                st.times.extend([t1] * (n - st.n))
                if cfg:
                    st.by_config[cfg] += n - st.n
                st.n = n
            p = r.prefilled
            if cfg and p > st.p:
                q = p - st.p
                work += q * st.p + q * (q + 1) // 2
            st.p = p
            if r.finish_reason is not None:
                del self.live[rid]
        if cfg:
            self.steps.append((t0, t1, cfg, rec.get("n_rows", 0),
                               rec["prefill_tokens"], rec["decode_tokens"],
                               work, rec["attn_ctx_tokens"]))
        return progressed

    def serve_open(self, items, t_base, t_end, i=0):
        """Open loop: submit each item when due (``Request.arrival`` is the
        due time), step while there is work, until ``t_end``. Returns the
        index of the next item to send."""
        while True:
            now = clock()
            if now >= t_end:
                return i
            while i < len(items) and t_base + items[i].due <= now:
                self.submit(items[i], t_base + items[i].due)
                i += 1
            if self.busy():
                self.step()
            else:
                nxt = t_base + items[i].due if i < len(items) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - clock()))

    def serve_backlog(self, items, start, queue, until):
        """Closed offline batch: keep ``queue`` requests waiting beyond the
        slots; step until ``until()`` holds. Returns the next item index."""
        i = start
        while not until():
            waiting = sum(1 for r in self.eng.queue if r.slot is None)
            while waiting < queue and i < len(items):
                self.submit(items[i], clock())
                i += 1
                waiting += 1
            self.step()
        return i


def slots_prefilled(eng, slots):
    """Every admitted request is past its prompt, and the slots are full
    or the queue's head waits for pool blocks (nothing was admitted)."""
    act = eng.active
    if not act or any(r.prefilled < len(r.prompt) for r in act):
        return False
    return len(act) == slots or any(r.slot is None for r in eng.queue)


# ------------------------------------------------------------------ trace
def _trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
