"""One run of one benchmark cell: set-up, the measured window, the traced
reading, and the comparison with the reference that decides ``correct``.

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and metrics (``bench/metrics/<name>.py``,
each with ``read(ctx) -> float | None``). The configuration's ``"model"``
names its model (``bench/models/<model>/``, by the contract in
``modelkit.py``): the weights, the reference, the work counts and the sizes
the program is checked against all come from there.

The window drives the system's own loop: ``ControlPlane.step`` (forecast →
balance → scale) over an ``ElasticClusterFrontend`` whose replicas decode
through one ``FleetGroup`` on the async path. Each iteration submits the
requests that have come due, calls ``plane.step(n)`` with the number
submitted since the previous tick, and stamps on the host clock every token
that newly appears in a request's output: that is when a client receives
it.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time
from contextlib import nullcontext

import numpy as np

import modelkit
from generator import Traffic, seed_words

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MODELS = os.path.join(BENCH, "models")
TRACE_SECONDS = 4.0        # the traced span at the start of a --trace 1 window
SAVED_TRACE_S = 0.25       # the part of it ``--save-trace`` keeps
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------- the cell
def enable_cache() -> None:
    """The program's persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one), holding every program
    however fast it compiled, so only a checkout's first run compiles."""
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str) -> dict:
    """The cell's workload entry, configuration, traffic mix and the
    metrics it reports, from ``BENCHMARK.json``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(work)}")
    return cell_from(work[name],
                     [m for m in bench["end_to_end"] if _applies(m, name)],
                     [m for m in bench["per_layer"] if _applies(m, name)])


def load_config(path: str) -> dict:
    """A configuration file, which has to name its model."""
    cfg = load_json(path)
    if "model" not in cfg:
        raise SystemExit(f"{path}: no \"model\" key naming a directory "
                         f"under {MODELS}")
    return cfg


def cell_from(workload: dict, end_to_end: list, per_layer: list) -> dict:
    """A cell from a workload entry (``config``, ``traffic``, ``chips``) and
    the metric entries it reports."""
    return {
        "workload": workload,
        "config": load_config(os.path.join(BENCH, "configs",
                                           workload["config"] + ".json")),
        "traffic": load_json(BENCH, "traffic", workload["traffic"] + ".json"),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def load_reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


_models: dict = {}


def load_model(cfg: dict):
    """The package ``<MODELS>/<cfg["model"]>/``, loaded by path once per
    directory under a module name of its own, so that two models in one
    process never shadow each other's modules."""
    path = os.path.join(MODELS, cfg["model"])
    if path not in _models:
        name = f"bench_model_{len(_models)}"
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(path, "__init__.py"),
            submodule_search_locations=[path])
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _models[path] = mod
    return _models[path]


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a config file: the registered
    published architecture with the file's ``arch`` overrides, checked
    against the file's sizes (the model's ``program_sizes``)."""
    from repro.configs import get_config

    arch = dataclasses.replace(get_config(cfg["registered"]),
                               name=cfg["name"], **cfg["arch"])
    want = load_model(cfg).program_sizes(cfg)
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise SystemExit(f"{cfg['name']}: the program's config {got} is not "
                         f"the file's {want}")
    return arch


def reduced(cell: dict, max_seq: int = 64) -> dict:
    """The cell at the program's tiny CPU size (``<arch>-reduced``, float32;
    the model's ``reduced_config``), with the traffic's lengths cut to fit
    ``max_seq``: for tests off the chip."""
    from repro.configs import get_config

    cfg = cell["config"]
    reg = cfg["registered"] + "-reduced"
    small = dict(load_model(cfg).reduced_config(cfg, get_config(reg)),
                 registered=reg, arch={},
                 fleet=dict(cfg["fleet"], max_seq=max_seq))
    tr = cell["traffic"]
    half = max_seq // 2

    def cut(spec):
        hi = min(spec["max"], half - 1)
        return dict(spec, max=hi, min=min(spec["min"], hi),
                    median=min(spec["median"], hi))

    traffic = dict(tr, prompt=cut(tr["prompt"]), output=cut(tr["output"]),
                   warmup_s=1.0, check_tokens=64)
    return dict(cell, config=small, traffic=traffic)


# ------------------------------------------------------------ recording
@dataclasses.dataclass
class Record:
    """One request as its client sees it."""
    req: object
    due: float                 # seconds on the host clock
    times: list = dataclasses.field(default_factory=list)   # token receipts
    done_at: float | None = None


class _CompileCount:
    """XLA compilations while attached (a JAX monitoring listener)."""

    def __init__(self):
        self.count = 0

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class _TimedBackend:
    """The frontend as the plane sees it, with the wall time of its ``tick``
    and the engine's blocked time during it recorded."""

    def __init__(self, fe):
        self._fe = fe
        self.tick_s = 0.0
        self.tick_wait_s = 0.0
        self.span = nullcontext

    def __getattr__(self, name):
        return getattr(self._fe, name)

    def tick(self, arrival_rate: float = 0.0):
        w0 = self._fe.sync_wait_s()
        t0 = time.perf_counter()
        with self.span("bench.fe_tick"):
            out = self._fe.tick(arrival_rate)
        self.tick_s += time.perf_counter() - t0
        self.tick_wait_s += self._fe.sync_wait_s() - w0
        return out


class Counters:
    """Host counters summed over a span of ticks."""

    def __init__(self):
        self.ticks = 0
        self.step_s = 0.0
        self.fe_tick_s = 0.0
        self.sync_wait_s = 0.0
        self.slots_allocated = 0
        self.decode_positions: list = []
        self.prompts: list = []


# ------------------------------------------------------------ the system
def build_system(cfg: dict, arch, params):
    import jax.numpy as jnp

    from repro.configs.paper_cluster import ClusterConfig
    from repro.control import ControlPlane
    from repro.core import balancer as bal
    from repro.serving import ElasticClusterFrontend, ReplicaEngine

    from repro.models.model import make_model

    fl, ctl = cfg["fleet"], cfg["control"]
    model = make_model(arch, tp=1)
    ccfg = ClusterConfig(
        num_nodes=fl["nodes"], horizon=ctl["horizon"],
        forecast_window=ctl["forecast_window"],
        provisioning_delay=ctl["provisioning_delay"],
        max_replicas_per_node=fl["max_replicas"], min_replicas_per_node=1,
        scale_interval=ctl["scale_interval"], cooldown=ctl["cooldown"],
        straggler_prob=0.0, node_mtbf=1e12)

    def make_replica(rid: int):
        # homogeneous replicas: speed 1 and one max_batch, so one fleet
        # group and one decode dispatch per tick
        return ReplicaEngine(model, params, max_batch=fl["max_batch"],
                             max_seq=fl["max_seq"], rid=rid, speed=1.0,
                             cache_dtype=jnp.dtype(arch.dtype))

    fe = ElasticClusterFrontend(
        make_replica, fl["nodes"], initial_replicas=fl["initial_replicas"],
        provisioning_delay=ctl["provisioning_delay"],
        max_replicas_per_node=fl["max_replicas"], request_factory=None,
        seed=ctl["seed"], est_tokens=ctl["est_tokens"],
        async_tick=ctl["async_tick"], decode_block=ctl["decode_block"])
    backend = _TimedBackend(fe)
    rl = bal.RLBalancer(ccfg, 4 + ccfg.horizon, seed=ctl["seed"]) \
        if ctl["balancer"] == "rl" else None
    plane = ControlPlane(ccfg, backend, balancer=ctl["balancer"],
                         scaler=ctl["scaler"],
                         unit_capacity=fl["max_batch"] / ctl["est_tokens"],
                         rl=rl, seed=ctl["seed"])
    return fe, backend, plane


def _fleet(fe):
    groups = list(fe._fleets.values())
    if len(groups) != 1:
        raise RuntimeError(f"expected one fleet group, have {len(groups)}")
    return groups[0]


def prefill_buckets(traffic: dict, max_seq: int, min_bucket: int) -> list:
    """Every prompt-length bucket the mix can reach."""
    from repro.serving.engine import pow2_bucket

    p = traffic["prompt"]
    return sorted({min(pow2_bucket(n, min_bucket), max_seq)
                   for n in range(p["min"], p["max"] + 1)})


def compile_prefill_shapes(fe, traffic: dict, log) -> int:
    """Compile, without running, the fleet prefill for every (batch bucket,
    length bucket) the mix can send at the slab's final capacity, so no
    admission compiles inside the window. A shape the chip's memory cannot
    hold is logged and skipped, with the larger batches of its length: the
    program could not admit that many such prompts in one tick. Returns the
    number of shapes compiled."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import pow2_bucket

    g = _fleet(fe)
    eng = g.members[0]
    kmax = pow2_bucket(fe.num_nodes * fe.max_replicas_per_node * g.max_batch)
    shapes = 0
    for sb in prefill_buckets(traffic, g.max_seq, eng.min_bucket):
        K = 1
        while K <= kmax:
            z = jnp.zeros((K,), jnp.int32)
            try:
                g._kernels.afleet_prefill.lower(
                    g.params, g.slab, g.ops, jnp.zeros((K, sb), jnp.int32),
                    z, z, z, z, z).compile()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                log(f"prefill of {K} x {sb} tokens does not fit the chip's "
                    f"memory at slab capacity {g.cap}: not compiled")
                break
            shapes += 1
            K *= 2
    return shapes


# -------------------------------------------------------------- driving
class Driver:
    """Feeds a traffic mix into the loop and records what clients see."""

    def __init__(self, fe, backend, plane, traffic: Traffic, *, span):
        self.fe, self.backend, self.plane = fe, backend, plane
        self.traffic = traffic
        self.span = span
        backend.span = span
        self.records: list = []
        self.live: list = []
        self.next_i = 0
        self.t0 = None
        self.rejected = 0

    def start(self, now: float):
        self.t0 = now

    def _submit_due(self, now: float) -> int:
        from repro.serving import Request

        n = 0
        while self.t0 + self.traffic.due(self.next_i) <= now:
            i = self.next_i
            self.next_i += 1
            _, out_len = self.traffic.lengths(i)
            req = Request(self.fe.alloc_rid(), self.traffic.prompt(i),
                          max_new_tokens=out_len)
            rec = Record(req, self.t0 + self.traffic.due(i))
            if self.fe.submit(req):
                self.records.append(rec)
                self.live.append(rec)
            else:
                self.rejected += 1
            n += 1
        return n

    def _stamp(self, now: float):
        keep = []
        for rec in self.live:
            n = len(rec.req.output)
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            if rec.req.done:
                rec.done_at = now
            else:
                keep.append(rec)
        self.live = keep

    def tick(self, counters: Counters | None = None):
        fe, backend = self.fe, self.backend
        now = time.perf_counter()
        with self.span("bench.submit"):
            n = self._submit_due(now)
        g = _fleet(fe)
        d0 = g.dispatches
        tick0, wait0 = backend.tick_s, backend.tick_wait_s
        t0 = time.perf_counter()
        with self.span("bench.plane_step"):
            self.plane.step(float(n))
        t1 = time.perf_counter()
        with self.span("bench.stamp"):
            if counters is not None:
                self._count(counters, g, d0, t1 - t0, tick0, wait0)
            self._stamp(t1)

    def _count(self, c: Counters, g, d0, step_s, tick0, wait0):
        c.ticks += 1
        c.step_s += step_s
        c.fe_tick_s += self.backend.tick_s - tick0
        c.sync_wait_s += self.backend.tick_wait_s - wait0
        c.slots_allocated += (g.dispatches - d0) * g.cap * g.max_batch
        for rec in self.live:
            req, seen = rec.req, len(rec.times)
            new = len(req.output) - seen
            if new <= 0:
                continue
            plen = len(req.prompt)
            for j in range(seen, seen + new):
                if j == 0:
                    c.prompts.append(plen)
                else:          # output j was written at cache position
                    c.decode_positions.append(plen + j - 1)

    def run_until(self, end: float, counters: Counters | None = None,
                  stop=None):
        while time.perf_counter() < end:
            self.tick(counters)
            if stop is not None and stop():
                return


# ------------------------------------------------------------- the run
def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def warm_up(drv: Driver, cfg: dict, traffic: dict, log):
    """Set-up after the weights: with no traffic yet, force the replica
    count to its maximum through ``scale_to`` (so the slab reaches its final
    capacity), drain one replica per node and bring it back (the slab's row
    edits), and compile every prefill shape at that capacity. Then the
    cell's own traffic runs through the whole loop until ``warmup_s`` has
    passed and the autoscaler has planned a few times."""
    fe, backend = drv.fe, drv.backend
    fl = cfg["fleet"]
    top = fl["max_replicas"]

    def settle(live):
        fe.scale_to(np.full(fl["nodes"], live))
        while not all(len(n.live) == live and not n.spawning
                      and not n.draining for n in fe.nodes):
            backend.tick(0.0)

    settle(top)
    settle(top - 1)
    settle(top)
    t0 = time.perf_counter()
    shapes = compile_prefill_shapes(fe, traffic, log)
    log(f"compiled {shapes} prefill shapes in "
        f"{time.perf_counter() - t0:.1f}s at slab capacity {_fleet(fe).cap}")
    drv.start(time.perf_counter())
    # its first ticks compile the plane's programs, and the autoscaler
    # plans every ``scale_interval`` ticks
    t_plane = drv.plane.t + 3 * cfg["control"]["scale_interval"] + 1
    drv.run_until(math.inf, stop=lambda: drv.plane.t >= t_plane and
                  time.perf_counter() >= drv.t0 + float(traffic["warmup_s"]))
    log(f"warm-up traffic {time.perf_counter() - drv.t0:.1f}s, "
        f"{len(drv.records)} requests, replicas per node "
        f"{[len(n.live) for n in fe.nodes]}")


class System:
    """A cell's system after set-up: the frontend, the plane over it, and
    the driver that has fed it the warm-up traffic."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def set_up(cell: dict, seed: int, trace: bool, *, log,
           require_tpu: bool = True) -> System:
    """Weights from the seed on the device, the system built from the
    configuration, and the warm-up (``warm_up``). Refuses any platform but
    the TPU unless ``require_tpu`` is false (tests on the CPU)."""
    import jax

    cfg, traffic = cell["config"], cell["traffic"]
    name = cell["workload"]["name"]
    chips = int(cell["workload"]["chips"])
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's platform is "
                         f"{platform!r} (no CPU fallback)")
    if len(devices) < chips:
        raise SystemExit(f"bench: {name} needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    if chips != 1:
        raise SystemExit(f"bench: {name} asks for {chips} chips; the "
                         f"harness builds one-chip cells only")
    used = devices[:chips]
    peaks = None
    if require_tpu:
        table = load_json(BENCH, "peaks.json")["devices"]
        if used[0].device_kind not in table:
            raise SystemExit(f"bench: no peaks for {used[0].device_kind!r}"
                             f" in peaks.json")
        peaks = table[used[0].device_kind]

    arch = arch_config(cfg)
    params = jax.block_until_ready(load_model(cfg).make_params(
        cfg, seed, dtype=jax.numpy.dtype(arch.dtype)))
    log(f"{cfg['name']}: weights from seed {seed} on "
        f"{chips} x {used[0].device_kind}")
    fe, backend, plane = build_system(cfg, arch, params)
    del params
    span = jax.profiler.TraceAnnotation if trace else (lambda _n: nullcontext())
    drv = Driver(fe, backend, plane, Traffic(traffic, cfg["vocab_size"], seed),
                 span=span)
    warm_up(drv, cfg, traffic, log)
    return System(cfg=cfg, traffic=traffic, fe=fe, backend=backend,
                  plane=plane, drv=drv, span=span, devices=devices,
                  used=used, platform=platform, peaks=peaks)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, log=print, require_tpu: bool = True,
             cell: dict | None = None, control: bool = False,
             save_trace: str | None = None) -> dict:
    """One run of cell ``name``. ``require_tpu=False`` and a ``cell`` from
    ``reduced`` run it on the CPU for tests; the platform is still named in
    the result, and no device metric can be read there. ``control`` also
    reads the float8 control on the same sample and judges it by the same
    limits (``control``), for setting the limit; the benchmark's own runs
    leave it off. ``save_trace`` writes the traced window's first
    ``SAVED_TRACE_S`` seconds, as ``devtrace.to_plain`` reads them, to that
    path (gzipped JSON)."""
    import jax

    cell = cell or load_cell(name)
    system = set_up(cell, seed, trace, log=log, require_tpu=require_tpu)
    cfg, traffic = system.cfg, system.traffic
    fe, drv, used = system.fe, system.drv, system.used

    # ------------------------------------------------ the measured window
    traces0 = fe.serve_kernel_traces()
    cap0 = _fleet(fe).cap
    counters = Counters()
    trace_dir = None
    with _CompileCount() as compiles:
        w0 = time.perf_counter()
        setup_s = w0 - t_start
        w1 = w0 + seconds
        if trace:
            import tempfile

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            tspan = min(seconds, TRACE_SECONDS)
            jax.profiler.start_trace(trace_dir)
            tw0 = time.perf_counter()
            drv.run_until(tw0 + tspan, counters)
            tw1 = time.perf_counter()
            jax.profiler.stop_trace()
            drv.run_until(w1)
        else:
            drv.run_until(w1, counters)
        w_end = time.perf_counter()
    window_traces = fe.serve_kernel_traces() - traces0
    g = _fleet(fe)
    waiting = sum(1 for r in drv.records if not r.times)
    log(f"window: {w_end - w0:.3f}s, {counters.ticks} ticks counted, "
        f"{len(drv.records)} requests sent, "
        f"{waiting} waiting for a first token at the close, serve-kernel "
        f"traces {window_traces}, XLA compiles {compiles.count}; slab "
        f"capacity {cap0} -> {g.cap} rows, {len(g.members)} members, "
        f"replicas per node {[len(n.live) for n in fe.nodes]}")
    memory_peak = _peak_bytes(used)

    ctx = {"cfg": cfg, "peaks": system.peaks, "flops": load_model(cfg),
           "records": drv.records, "window": (w0, w1), "setup_s": setup_s,
           "counters": counters, "trace": None}
    result_device = {"platform": system.platform, "kind": used[0].device_kind,
                     "count": len(system.devices),
                     "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        import shutil

        import devtrace

        planes = devtrace.load_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if save_trace:
            devtrace.save(planes, save_trace, SAVED_TRACE_S)
        red = devtrace.reduce(planes, len(used))
        del planes
        ctx["trace"] = red
        ctx["window"] = (tw0, tw1)
        result_device["busy_s"] = red["busy_s"]
        result_device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["top_programs"],
                     "idle_gaps": red["idle_gaps"]}
    metrics = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        val = load_reader(m["name"])(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    # -------------------------------------- after the window: correctness
    due = [r for r in drv.records if w0 <= r.due < w1]
    finished = [r for r in drv.records
                if r.done_at is not None and w0 <= r.done_at <= w1]
    state = {"double_served": fe.ledger.double_served,
             "short": sum(len(r.req.output) != r.req.max_new_tokens
                          for r in drv.records if r.req.done),
             "rejected": drv.rejected}
    max_seq = cfg["fleet"]["max_seq"]
    del system, fe, drv, g
    gc.collect()

    def judge(control_run: bool) -> dict:
        checks = check_outputs(cfg, seed, finished, max_seq,
                               int(traffic["check_tokens"]),
                               control=control_run)
        log(f"{'control: ' if control_run else ''}checked "
            f"{checks.pop('checked_tokens')} served tokens of "
            f"{len(finished)} requests finished in the window")
        checks["double_served"] = {"value": state["double_served"],
                                   "limit": 0}
        checks["short_outputs"] = {"value": state["short"], "limit": 0}
        return checks

    def passes(checks: dict) -> bool:
        return bool(finished) and all(c["value"] <= c["limit"]
                                      for c in checks.values())

    checks = judge(False)
    out = {"correct": passes(checks), "attempted": len(due),
           "failed": state["rejected"],
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        ctl = judge(True)
        out["control"] = {"correct": passes(ctl), "checks": ctl}
    out["checks"] = checks
    return out


def sample(finished: list, seed: int, tokens: int) -> list:
    """Requests drawn from the seed, the longest first, until they hold
    ``tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r.req.output), r.req.rid))
    rest = order[1:]
    rng = np.random.default_rng([*seed_words(seed), 3])
    rng.shuffle(rest)
    out, n = [order[0]], len(order[0].req.output)
    for r in rest:
        if n >= tokens:
            break
        out.append(r)
        n += len(r.req.output)
    return out


def check_outputs(cfg: dict, seed: int, finished: list, max_seq: int,
                  tokens: int, *, control: bool = False) -> dict:
    """The widest gap, over the sampled requests' served tokens, by which a
    served token's reference logit lies below the reference's best (in
    units of that row's standard deviation) under the model's reference,
    and the count of token ids outside the vocabulary."""
    logits = load_model(cfg).logits
    picked_reqs = sample(finished, seed, tokens)
    vocab = cfg["vocab_size"]
    bad = sum(not 0 <= t < vocab for r in picked_reqs for t in r.req.output)
    rows = max(1, min(16, int(2 ** 31 // (max_seq * vocab * 4))))
    widest, served = 0.0, 0
    for i in range(0, len(picked_reqs), rows):
        chunk = [r.req for r in picked_reqs[i:i + rows]]
        toks = np.zeros((len(chunk), max_seq), np.int32)
        picked = np.zeros((len(chunk), max_seq), np.int32)
        mask = np.zeros((len(chunk), max_seq), bool)
        for b, req in enumerate(chunk):
            seq = list(req.prompt) + [t if 0 <= t < vocab else 0
                                      for t in req.output]
            seq = seq[:max_seq + 1]
            toks[b, :len(seq) - 1] = seq[:-1]
            p = len(req.prompt)
            n = len(seq) - p
            picked[b, p - 1:p - 1 + n] = seq[p:]
            mask[b, p - 1:p - 1 + n] = True
            served += n
        gaps = modelkit.served_gaps(logits, cfg, seed, toks, picked,
                                    control=control,
                                    dtype=np.dtype(cfg["torch_dtype"]))
        widest = max(widest, float(np.max(np.where(mask, gaps, 0.0))))
    limit = float(cfg["limits"]["logit_gap"])
    return {"logit_gap": {"value": widest, "limit": limit},
            "bad_token_ids": {"value": bad, "limit": 0},
            "checked_tokens": served}
