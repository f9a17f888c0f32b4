"""Drive the serving system once on a TPU at granite-3-8b's published widths.

    python chip_smoke.py             # one chip: the whole main path
    python chip_smoke.py --chips 4   # four chips: sharded fleet vs one chip

One chip serves ``granite-3-8b-1chip`` (hidden 4096, 32/8 heads of 128,
d_ff 12800, vocab 49155, 20 of the 40 layers, bf16 weights and KV cache,
random weights from a seed) through ``launch.serve.run_control_loop``: the
forecast -> balance -> scale loop (RL balancer, GPSO autoscaler) over an
``ElasticClusterFrontend`` of 2 nodes x up to 2 replicas, ``max_batch`` 4,
``max_seq`` 1024. Before serving it checks prefill logits against the full
forward pass and the Pallas ``flash_decode`` kernel against its reference.
``--chips 4`` runs only the fleet slab sharded over a 4-chip ``('fleet',)``
mesh and the one-chip run it is compared with.

Every check raises on failure, so the script exits non-zero. It refuses any
platform but TPU (no CPU fallback). Its last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
``smoke`` and ``sharded_smoke`` are the bodies; the tests run them on the
CPU at the reduced config.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "granite-3-8b-1chip"
HBM_BYTES = 16e9                  # one TPU v5e (Google Cloud, "TPU v5e")
NODES, MAX_REPLICAS, MAX_BATCH = 2, 2, 4
# bf16 keeps 8 significant bits (unit roundoff 2**-8). The cached prefill
# and the full forward evaluate the same bf16 network in different orders,
# so activations may round apart by an ulp in any of the 20 layers. Allow
# the logit error relative to the logits' own RMS to reach 2**-4 (16 ulps
# of drift); a network run at a coarser 4-bit mantissa (fp8 e4m3) would
# miss it on rounding alone.
LOGIT_RTOL = 2.0 ** -4
# flash_decode and the reference both accumulate in f32 and round the
# output to bf16 once: the repo's bf16 kernel tolerance (tests/test_kernels)
KERNEL_TOL = 3e-2


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class _CompileClock:
    """Seconds XLA spent compiling while attached (a process-wide JAX
    monitoring listener, detached on exit)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def _peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _nbytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def check_logits(model, params, max_seq: int, seed: int) -> float:
    """Last-token logits of the cached prefill (the serving path) against
    the full forward pass on the same bf16 params, for prompts of a few
    lengths right-padded into one batch. Returns the worst relative error
    (max |diff| over the forward logits' RMS)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    vocab = model.cfg.vocab_size
    lengths = np.array([5, min(100, max_seq // 2), min(256, max_seq - 1)])
    S = int(lengths.max())
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(seed + 1),
                                         (len(lengths), S), 1, vocab))
    toks = np.where(np.arange(S)[None] < lengths[:, None], toks, 0)
    batch = {"tokens": jnp.asarray(toks, jnp.int32)}
    full, _ = jax.jit(model.forward)(params, batch)
    pre, _, _ = jax.jit(lambda p, b: model.prefill(
        p, b, cache_len=max_seq, cache_dtype=jnp.dtype(model.cfg.dtype)))(
        params, dict(batch, lengths=jnp.asarray(lengths, jnp.int32)))
    full = np.asarray(full, np.float32)
    pre = np.asarray(pre, np.float32)
    worst = 0.0
    for b, n in enumerate(lengths):
        want = full[b, n - 1]
        rms = float(np.sqrt(np.mean(want ** 2)))
        err = float(np.max(np.abs(pre[b] - want))) / rms
        _log(f"logits: prompt len {n}: max|prefill - forward| / rms = "
             f"{err:.4g} (rms {rms:.4g}, tol {LOGIT_RTOL:.4g})")
        if not np.all(np.isfinite(pre[b])):
            raise AssertionError(f"non-finite prefill logits at len {n}")
        worst = max(worst, err)
    if worst > LOGIT_RTOL:
        raise AssertionError(f"prefill logits off the forward pass: "
                             f"{worst:.4g} > {LOGIT_RTOL:.4g}")
    return worst


def check_kernel(cfg, batch: int, max_seq: int, seed: int) -> float:
    """One ``flash_decode`` call at the serving decode shapes (every slot of
    the fleet, its own cache depth) against ``decode_attention_ref``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import flash_decode
    from repro.kernels.ref import decode_attention_ref

    dtype = jnp.dtype(cfg.dtype)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    kq, kk, kv, kp = jax.random.split(jax.random.PRNGKey(seed + 2), 4)
    q = jax.random.normal(kq, (batch, hq, d), dtype)
    kc = jax.random.normal(kk, (batch, hkv, max_seq, d), dtype)
    vc = jax.random.normal(kv, (batch, hkv, max_seq, d), dtype)
    pos = jax.random.randint(kp, (batch,), 0, max_seq)
    # interpret mode is the CPU test mode only: main() refuses non-TPU
    interpret = jax.devices()[0].platform != "tpu"
    got = jax.jit(lambda *a: flash_decode(*a, interpret=interpret))(
        q, kc, vc, pos)
    want = decode_attention_ref(q, kc, vc, pos)
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32))))
    _log(f"flash_decode (B={batch}, {hq}/{hkv} heads, S={max_seq}, d={d}, "
         f"per-row pos, interpret={interpret}): max|kernel - ref| = "
         f"{err:.4g} (tol {KERNEL_TOL:g})")
    if not err <= KERNEL_TOL:
        raise AssertionError(f"flash_decode off its reference: {err:.4g}")
    return err


def _sizing(model, max_seq: int, param_bytes: int) -> dict:
    """Weights + the largest fleet slab + one eager copy of it (slab growth
    and row edits copy the slab while the old one is alive)."""
    import jax
    import jax.numpy as jnp

    per_replica = _nbytes(jax.eval_shape(lambda: model.init_serve_state(
        MAX_BATCH, max_seq, jnp.dtype(model.cfg.dtype))))
    slab = NODES * MAX_REPLICAS * per_replica
    need = param_bytes + 2 * slab
    _log(f"sizing: weights {param_bytes / 1e9:.3f} GB + slab "
         f"{NODES}x{MAX_REPLICAS} replicas x {MAX_BATCH} slots x {max_seq} "
         f"positions = {slab / 1e9:.3f} GB + one slab copy "
         f"= {need / 1e9:.3f} GB of {HBM_BYTES / 1e9:.0f} GB")
    if need > HBM_BYTES:
        raise AssertionError("weights + slab + copy exceed one chip")
    return {"slab_bytes": slab, "need_bytes": need}


def smoke(arch: str = ARCH, *, max_seq: int = 1024, ticks: int = 20,
          seed: int = 0) -> dict:
    """The one-chip run: logits and kernel checks, then a few dozen
    requests through the control loop. Raises on any failed check."""
    import jax

    from repro.configs import get_config
    from repro.launch.serve import build_parser, run_control_loop
    from repro.models.model import make_model

    cfg = get_config(arch)
    model = make_model(cfg, tp=1)
    dev = jax.devices()[0]
    out = {"arch": cfg.name, "device_kind": dev.device_kind}
    with _CompileClock() as clock:
        params = jax.block_until_ready(
            model.init(jax.random.PRNGKey(seed), cfg.dtype))
    out["param_bytes"] = _nbytes(params)
    _log(f"{cfg.name} on {dev.platform} ({dev.device_kind}): "
         f"{cfg.num_layers} layers, d_model {cfg.d_model}, "
         f"{cfg.num_heads}/{cfg.num_kv_heads} heads of "
         f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
         f"{cfg.vocab_size}, {cfg.dtype}; params "
         f"{out['param_bytes'] / 1e9:.3f} GB (init compile "
         f"{clock.seconds:.1f}s)")
    out.update(_sizing(model, max_seq, out["param_bytes"]))
    out["logit_rel_err"] = check_logits(model, params, max_seq, seed)
    out["kernel_err"] = check_kernel(
        cfg, NODES * MAX_REPLICAS * MAX_BATCH, max_seq, seed)

    args = build_parser().parse_args([
        "--arch", arch, "--policy", "ours", "--autoscale", "gpso",
        "--nodes", str(NODES), "--replicas", "1",
        "--max-replicas", str(MAX_REPLICAS), "--max-batch", str(MAX_BATCH),
        "--max-seq", str(max_seq), "--ticks", str(ticks), "--rate", "2",
        "--seed", str(seed)])
    t0 = time.perf_counter()
    with _CompileClock() as clock:
        fe = run_control_loop(args, cfg, model, params)
    out["serve_seconds"] = time.perf_counter() - t0
    out["compile_seconds"] = clock.seconds
    out["serve_kernel_traces"] = fe.serve_kernel_traces()
    done = fe.finished
    out["requests"] = len(done)
    out["tokens"] = sum(len(r.output) for r in done)
    led = fe.ledger
    states = led.balance()
    out["balanced"] = led.balanced()
    out["double_served"] = led.double_served
    out["peak_bytes_in_use"] = _peak_bytes([dev])[0]
    _log(f"served {out['requests']} requests, {out['tokens']} tokens in "
         f"{out['serve_seconds']:.1f}s wall (compiles included); "
         f"serve-kernel compiles {out['serve_kernel_traces']}, XLA compile "
         f"{clock.seconds:.1f}s over {clock.count} programs; ledger "
         f"balanced={out['balanced']} double_served={out['double_served']};"
         f" peak_bytes_in_use {out['peak_bytes_in_use']}")
    if not (out["balanced"] and out["double_served"] == 0):
        raise AssertionError(f"ledger not exactly-once: {states}")
    if out["requests"] == 0 or states["finished"] != led.submitted:
        raise AssertionError(f"not every request finished: {states}")
    for r in done:
        # no EOS, no deadline: every request runs to its token budget, and
        # every token is a real vocabulary id
        if len(r.output) != r.max_new_tokens or not all(
                0 <= t < cfg.vocab_size for t in r.output):
            raise AssertionError(f"request {r.rid}: bad output {r.output}")
    return out


def _fixed_requests(vocab: int, n: int, seed: int):
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(1, vocab, int(rng.integers(9, 17)))
                    .tolist(), max_new_tokens=12) for i in range(n)]


def _serve_fixed(model, params, max_seq: int, seed: int, mesh):
    """All slots of 2 nodes x 2 replicas filled at once, decoded to the end
    through the fleet path (sharded over ``mesh`` when given)."""
    import jax.numpy as jnp

    from repro.serving import ElasticClusterFrontend, ReplicaEngine

    def make_replica(rid):
        return ReplicaEngine(model, params, max_batch=MAX_BATCH,
                             max_seq=max_seq, rid=rid,
                             cache_dtype=jnp.dtype(model.cfg.dtype))

    fe = ElasticClusterFrontend(make_replica, NODES,
                                initial_replicas=MAX_REPLICAS,
                                max_replicas_per_node=MAX_REPLICAS,
                                seed=seed, mesh=mesh)
    reqs = _fixed_requests(model.cfg.vocab_size,
                           NODES * MAX_REPLICAS * MAX_BATCH, seed)
    for r in reqs:
        fe.submit(r)
    fe.run_until_drained()
    if not fe.ledger.balanced() or len(fe.finished) != len(reqs):
        raise AssertionError(f"fixed run did not finish: "
                             f"{fe.ledger.balance()}")
    return {r.rid: list(r.output) for r in reqs}, fe.serve_kernel_traces(), \
        reqs


def _near_tie(model, params, prompt, common, a: int, b: int) -> float:
    """The gap between the logits of tokens ``a`` and ``b`` after
    ``prompt + common`` (full forward on the same params), relative to the
    logits' RMS: where two runs pick different tokens, both must sit
    within the logit tolerance of each other."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(common)
    width = 1 << (len(seq) - 1).bit_length()      # one compile for all
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(seq)] = seq
    logits, _ = jax.jit(model.forward)(params, {"tokens": jnp.asarray(toks)})
    row = np.asarray(logits[0, len(seq) - 1], np.float32)
    rms = float(np.sqrt(np.mean(row ** 2)))
    return abs(float(row[a] - row[b])) / rms


def sharded_smoke(arch: str = ARCH, *, n_devices: int = 4,
                  max_seq: int = 1024, seed: int = 0) -> dict:
    """The same fixed requests through the fleet slab on one device and
    sharded over an ``n_devices`` ``('fleet',)`` mesh, one run after the
    other (each frees its arrays first). Streams must agree; where a
    token differs, the two choices must be a near-tie."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.mesh import make_fleet_mesh
    from repro.models.model import make_model

    cfg = get_config(arch)
    devices = jax.devices()[:n_devices]
    out = {"arch": cfg.name, "devices": len(devices)}

    def run(mesh):
        model = make_model(cfg, tp=1)          # fresh kernels per run
        sharding = None if mesh is None else NamedSharding(mesh, P())
        t0 = time.perf_counter()
        with _CompileClock() as clock:
            params = model.init(jax.random.PRNGKey(seed), cfg.dtype,
                                sharding=sharding)
            streams, traces, reqs = _serve_fixed(model, params, max_seq,
                                                 seed, mesh)
        label = "one device" if mesh is None else f"{n_devices}-way mesh"
        _log(f"{label}: {len(streams)} requests in "
             f"{time.perf_counter() - t0:.1f}s wall, serve-kernel compiles "
             f"{traces}, XLA compile {clock.seconds:.1f}s; peak bytes per "
             f"device {_peak_bytes(devices)}")
        return model, params, streams, reqs

    _, params, base, reqs = run(None)
    param_bytes = _nbytes(params)
    del params                      # the one-device weights go first
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    _log(f"between runs: {live} bytes of live arrays "
         f"(weights {param_bytes})")
    if live >= param_bytes // 2:
        raise AssertionError(f"the one-device run left {live} bytes live")
    mesh = make_fleet_mesh(n_devices)
    model, params, shard, _ = run(mesh)
    differ, worst_gap = 0, 0.0
    for r in reqs:
        a, b = base[r.rid], shard[r.rid]
        if a == b:
            continue
        differ += 1
        i = next(k for k, (x, y) in enumerate(zip(a, b)) if x != y)
        gap = _near_tie(model, params, r.prompt, a[:i], a[i], b[i])
        worst_gap = max(worst_gap, gap)
        _log(f"request {r.rid}: streams part at token {i} ({a[i]} vs "
             f"{b[i]}); logit gap / rms = {gap:.4g} (tol {LOGIT_RTOL:.4g})")
    out.update(requests=len(reqs), differ=differ, worst_gap=worst_gap)
    _log(f"sharded vs one device: {len(reqs) - differ}/{len(reqs)} streams "
         f"identical")
    if worst_gap > LOGIT_RTOL:
        raise AssertionError(f"streams part at a logit gap of "
                             f"{worst_gap:.4g}, beyond {LOGIT_RTOL:.4g}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the main path on one chip; 4: only the "
                         "sharded fleet and the one-chip run it is "
                         "compared with")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: cannot import the repro package from "
                 f"{os.path.join(ROOT, 'src')}: {e}")
    enable_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's platform is "
                 f"{platform!r} (no CPU fallback)")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU devices, JAX sees {len(devices)}")
    if args.chips == 1:
        smoke()
    else:
        sharded_smoke(n_devices=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
