"""End-to-end serving driver: the paper's full control plane over an elastic
request-level cluster of real model replicas.

Two modes:

  * **Unified control loop** (the paper's system, §3) — when ``--autoscale``
    is set or ``--policy ours``: builds an ``ElasticClusterFrontend`` (N
    nodes of heterogeneous ``ReplicaEngine``s with cold-start provisioning,
    graceful drain and failure injection) and drives it with the same
    ``ControlPlane`` (forecast -> balance -> scale) that runs the fluid
    simulator, over a bursty synthetic trace:

        PYTHONPATH=src python -m repro.launch.serve --policy ours \
            --autoscale gpso --ticks 60

    ``--policy ours`` uses the MADRL (GCN+DDPG) balancer acting greedily
    (training it belongs to the cheap fluid simulator — see
    ``examples/autoscale_sim.py``); ``--autoscale gpso`` runs the Eq.9-11
    GPSO planner against the live replica counts.

  * **Legacy drain mode** — ``--policy rr|lc|fractions`` with
    ``--autoscale none``: a fixed batch of requests through the static
    ``ClusterFrontend``, reporting throughput + TTFT/latency percentiles.

Both report prefill retrace counts: prompts are padded to power-of-two
buckets so the engine compiles O(log max_seq) prefill variants total.

Admission flags: ``--chunk-len N`` streams prompts longer than N in fixed
chunks interleaved with decode (bounded TTFT/TBT tail); the fleet batches
all same-bucket admits across replicas into one jitted prefill per distinct
bucket shape per tick; ``--tiers premium:0.2:w5:4,standard:0.5:w2,
batch:0.3:w1`` serves an SLO-tiered mix (share : weighted-deficit weight :
optional TTFT target) through tiered replica queues, per-tier metrics and
the tier-weighted Eq.5/Eq.9 objectives — the default single tier is
bit-identical to the untiered scheduler.

Tick-overlap flags: the serve tick is asynchronous — fleet dispatches
return device futures reconciled at ONE host sync per tick, so host
bookkeeping and the control plane overlap the device's decode;
``--decode-block K`` fuses K decode micro-steps into one dispatch
and one sync on ticks that admit nothing (saturated decode pays 1/K syncs
per tick, trading up to K-1 ticks of admission lag under a full slab);
``--attn-backend pallas`` decodes attention through the flash-decode
kernel (interpret mode off-TPU) instead of the dense einsum.

Multi-cell flags: ``--cells N`` federates N elastic cells behind the
fault-tolerant routing plane (``control.cells.MultiCellBackend``) — the
same control plane drives the federation with cells as its "nodes";
``--cell-chaos 'cell_down@15:c0,partition@10:c1:k6,cell_up@30:c0'``
scripts blackouts and control-plane partitions (node-level ``--chaos``
lands on cell 0); ``--shed-threshold X`` arms total-overload admission
shedding (lowest tiers first, explicit ``shed`` ledger terminal);
``--static-split`` is the A/B arm that routes a fixed uniform split.

Hierarchy flags (PR 10): ``--hierarchy`` splits control in two —
per-cell ``CellController`` autoscalers act every tick inside capacity
leases that a ``GlobalPlanner`` re-grants every
``--plan-interval-global`` ticks with ``--lease-slack`` headroom, all
under a crash-tolerant ``PlaneSupervisor`` (the ``ControlPlane`` runs
forecast+balance only; scaling authority belongs to the leases).
``--cell-chaos 'plane_down@10:k6'`` crashes the GLOBAL plane: the
centralized loop freezes (no planning, no balancing) while the
hierarchical loop keeps autoscaling locally inside the last leases, so
its scale-reaction latency stays short through the outage.
``slow@t:nI:xF`` in ``--chaos`` pins a deterministic straggler
(node I at 1/F speed until ``x1`` clears it).

Device scaling: ``--devices N`` shards every fleet group's slab over an
N-way ``('fleet',)`` mesh so F replicas decode on N devices in parallel
(same one-logical-dispatch / one-sync tick; bit-identical streams). On a
CPU box this exposes N *virtual* devices by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — which must happen
BEFORE the first jax import, which is why this module defers ``import jax``
into ``main()`` and errors clearly if jax already initialized.
``--mesh '4:fleet'`` passes an explicit mesh spec instead (a real
multi-chip mesh on GPU/TPU needs no XLA_FLAGS trick).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _percentiles(xs, qs=(50, 95)):
    xs = np.asarray(xs, np.float64)
    return [float(np.percentile(xs, q)) for q in qs]


def _parse_timeout(spec: str):
    """'8' -> scalar ticks; 'premium:4,batch:16' -> per-tier dict."""
    try:
        return float(spec)
    except ValueError:
        out = {}
        for part in filter(None, (p.strip() for p in spec.split(","))):
            name, _, val = part.partition(":")
            if not val:
                raise ValueError(f"bad timeout entry {part!r}")
            out[name] = float(val)
        return out


def run_control_loop(args, cfg, model, params, mesh=None):
    """Drive the elastic frontend with the forecast -> balance -> scale loop
    for ``args.ticks`` ticks, drain it, print the report and return the
    frontend (its ``finished`` requests, ledger and kernel counters)."""
    import jax.numpy as jnp

    from repro.configs.paper_cluster import ClusterConfig
    from repro.control import (CellController, CellRouter, ControlPlane,
                               GlobalPlanner, MultiCellBackend,
                               PlaneSupervisor)
    from repro.core import balancer as bal
    from repro.serving import (ChaosSchedule, ElasticClusterFrontend,
                               ReplicaEngine, Request)
    from repro.workload import (ClientPool, TraceConfig, generate_trace,
                                parse_tiers)

    tiers = parse_tiers(args.tiers)
    multi = args.cells > 1
    if args.hierarchy and not multi:
        raise SystemExit("--hierarchy needs --cells > 1 (the two-level "
                         "split is over a federation of cells)")
    # multi-cell: the plane sees CELLS as nodes; a scale target is the
    # cell's total replica budget, so the per-"node" cap scales with the
    # cell's own node count
    ccfg = ClusterConfig(
        num_nodes=args.cells if multi else args.nodes,
        horizon=8, forecast_window=16,
        provisioning_delay=args.provision_delay,
        max_replicas_per_node=(args.nodes * args.max_replicas
                               if multi else args.max_replicas),
        min_replicas_per_node=1,      # never plan a node to zero capacity
        scale_interval=5, cooldown=8, straggler_prob=0.0, node_mtbf=1e12)
    rng = np.random.default_rng(args.seed)

    def make_replica(rid: int) -> ReplicaEngine:
        # heterogeneous pool: mixed hardware generations + batch budgets
        speed = float(rng.choice([0.7, 1.0, 1.4]))
        mb = int(rng.choice([max(2, args.max_batch // 2), args.max_batch]))
        return ReplicaEngine(model, params, max_batch=mb,
                             max_seq=args.max_seq, rid=rid, speed=speed,
                             cache_dtype=jnp.dtype(cfg.dtype),
                             chunk_len=args.chunk_len, tiers=tiers,
                             attn_backend=args.attn_backend)

    def request_factory(rid: int, tick: int) -> Request:
        plen = int(rng.integers(2, 12))
        req = Request(rid, rng.integers(1, cfg.vocab_size, plen).tolist(),
                      max_new_tokens=int(rng.integers(4, 12)))
        if len(tiers) > 1:     # single-tier: no extra rng draw, so default
            req.tier = tiers.sample(rng)      # invocations stay bit-exact
        return req

    est_tokens = 8.0
    chaos = ChaosSchedule.parse(args.chaos) if args.chaos else None

    def build_cell(cell_chaos):
        return ElasticClusterFrontend(
            make_replica, args.nodes, initial_replicas=args.replicas,
            provisioning_delay=args.provision_delay,
            max_replicas_per_node=args.max_replicas,
            failure_rate=args.failure_rate, request_factory=request_factory,
            seed=args.seed, est_tokens=est_tokens,
            decode_block=args.decode_block,
            tiers=tiers, mesh=mesh,
            preempt_notice=args.preempt_notice, chaos=cell_chaos)

    if multi:
        # node-level --chaos lands on cell 0 (the scripted victim); cell
        # events drive the router
        cell_chaos = ChaosSchedule.parse(args.cell_chaos) \
            if args.cell_chaos else None
        router = CellRouter(
            args.cells, tiers=tiers,
            shed_threshold=args.shed_threshold or None,
            adaptive=not args.static_split)
        fe = MultiCellBackend(
            [build_cell(chaos if c == 0 else None)
             for c in range(args.cells)],
            tiers=tiers, router=router, chaos=cell_chaos,
            request_factory=request_factory, seed=args.seed)
    else:
        fe = build_cell(chaos)
    pool = None
    if args.clients > 0:
        # closed loop: the pool replaces the open-loop arrival trace (the
        # frontend's request_factory goes unused at arrival_rate 0)
        pool = ClientPool(
            fe, args.clients, request_factory=request_factory,
            think_time=args.think_time,
            timeout=_parse_timeout(args.timeout),
            max_retries=args.retries, spawn_rate=args.spawn_rate,
            seed=args.seed + 1)

    balancer = {"ours": "rl", "rr": "rr", "lc": "lc", "wrr": "wrr",
                "fractions": "wrr"}[args.policy]
    rl = None
    if balancer == "rl":
        rl = bal.RLBalancer(ccfg, 4 + ccfg.horizon, seed=args.seed)
    unit_cap = args.max_batch / est_tokens     # replica requests/tick
    trace = generate_trace(TraceConfig(ticks=args.ticks, base_rate=args.rate,
                                       diurnal_period=max(args.ticks, 2)),
                           seed=args.seed)
    arrivals = trace["arrivals"]
    # hierarchy mode: the ControlPlane keeps forecast + balance, but
    # scaling authority moves to the per-cell controllers under leases
    plane = ControlPlane(ccfg, fe, balancer=balancer,
                         scaler="none" if args.hierarchy
                         else args.autoscale,
                         unit_capacity=unit_cap,
                         rl=rl, forecast_scale=float(arrivals.mean()),
                         seed=args.seed,
                         init_arrival=float(arrivals[:5].mean()))
    sup = None
    if args.hierarchy:
        cell_cap = args.nodes * args.max_replicas
        planner = GlobalPlanner(args.cells,
                                total_budget=args.cells * cell_cap,
                                max_per_cell=cell_cap,
                                lease_slack=args.lease_slack)
        controllers = [CellController(fe, c) for c in range(args.cells)]
        sup = PlaneSupervisor(fe, planner, controllers, plane=plane,
                              plan_interval=args.plan_interval_global)

    print(f"[serve] unified loop: balancer={balancer} "
          f"autoscale={args.autoscale} nodes={args.nodes} "
          f"ticks={args.ticks}"
          + (f" cells={args.cells}" if multi else "")
          + (" hierarchy=on"
             f" plan-interval={args.plan_interval_global}" if sup else "")
          + (f" clients={args.clients}" if pool else "")
          + (f" chaos={args.chaos!r}" if chaos else "")
          + (f" cell-chaos={args.cell_chaos!r}"
             if multi and args.cell_chaos else ""))
    t0 = time.time()
    for t in range(args.ticks):
        if pool is not None:
            pool.tick()                     # closed loop drives arrivals
        rate = 0.0 if pool is not None else float(arrivals[t])
        if sup is not None:
            m = sup.step(rate)
        elif getattr(fe, "plane_alive", True):
            m = plane.step(rate)
        else:
            # centralized baseline under a plane outage: the one brain is
            # gone — tick the data plane, no planning/balancing/scaling
            m = fe.tick(rate)
        if t % 10 == 0 or t == args.ticks - 1:
            print(f"[serve] t={t:3d} arrivals={arrivals[t]:5.1f}/tick "
                  f"replicas={m['active_replicas'].tolist()} "
                  f"queue={m['queue'].astype(int).tolist()} "
                  f"util={m['mean_utilization']:.2f} "
                  f"resp={m['response_time']:.1f}t "
                  f"goodput={m['goodput']:.0f}")
    if pool is not None:
        pool.quiesce()
    fe.run_until_drained()
    if pool is not None:
        pool.finalize()
    wall = time.time() - t0

    done = fe.finished
    toks = sum(len(r.output) for r in done)
    traces = fe.prefill_retraces()
    print(f"[serve] {len(done)} requests, {toks} tokens in {wall:.2f}s "
          f"({toks / max(wall, 1e-9):.1f} tok/s); "
          f"replicas spawned={fe.replicas_spawned} "
          f"failed={fe.failed_replicas} "
          f"replica-ticks={fe.replica_ticks} "
          f"decode-dispatches={fe.decode_dispatches()} "
          f"prefill-dispatches={fe.prefill_dispatches()} "
          f"syncs={fe.sync_count()} "
          f"sync-wait={fe.sync_wait_s():.2f}s")
    # queue-culled deadline expiries land in fe.finished with NO first
    # token (ledger resolves them timed-out) — latency stats are over
    # requests that were actually served
    served = [r for r in done if r.first_token_time is not None]
    if served:
        ttft = _percentiles([r.first_token_time - r.arrival
                             for r in served])
        lat = _percentiles([r.finish_time - r.arrival for r in served])
        print(f"[serve] TTFT p50={ttft[0]:.1f} p95={ttft[1]:.1f} ticks; "
              f"latency p50={lat[0]:.1f} p95={lat[1]:.1f} ticks; "
              f"prefill retraces={traces}")
        if len(tiers) > 1:
            for spec in tiers.specs:
                sub = [r for r in served if tiers.index(r.tier)
                       == tiers.index(spec.name)]
                if not sub:
                    continue
                tt = _percentiles([r.first_token_time - r.arrival
                                   for r in sub])
                att = ""
                if np.isfinite(spec.ttft_target):
                    ok = np.mean([r.first_token_time - r.arrival
                                  <= spec.ttft_target for r in sub])
                    att = f" SLO({spec.ttft_target:g}t)={ok:.0%}"
                print(f"[serve]   tier {spec.name:<10} n={len(sub):4d} "
                      f"TTFT p50={tt[0]:.1f} p95={tt[1]:.1f}{att}")

    # ------------------------------------------------ robustness report
    led = fe.ledger
    states = led.balance()
    print(f"[serve] ledger: submitted={led.submitted} "
          f"finished={states['finished']} timed_out={states['timed_out']} "
          f"abandoned={states['abandoned']} rejected={states['rejected']} "
          f"shed={states['shed']} "
          f"retries={led.retries} duplicates={led.duplicates} "
          f"wasted={led.wasted} double_served={led.double_served} "
          f"balanced={led.balanced()}")
    for tname, row in sorted(led.per_tier.items()):
        total = max(row["finished"] + row["timed_out"]
                    + row["abandoned"] + row["rejected"], 1)
        print(f"[serve]   ledger tier {tname:<10} "
              f"goodput={row['finished']}/{total} "
              f"({row['finished'] / total:.0%}) "
              f"timed_out={row['timed_out']} abandoned={row['abandoned']} "
              f"rejected={row['rejected']} shed={row['shed']} "
              f"retries={row['retries']}")
    if fe.preempted_nodes or fe.preempted_replicas:
        print(f"[serve] preemptions: nodes={fe.preempted_nodes} "
              f"replicas={fe.preempted_replicas}")
    if multi:
        # degraded-mode report: what the routing plane absorbed
        stale = fe.cell_staleness().astype(int).tolist()
        print(f"[serve] cells: downs={fe.cell_downs} "
              f"evacuated={fe.evacuated_total} shed={fe.shed_total} "
              f"quarantine-ticks={fe.quarantine_ticks} "
              f"parked={len(fe.pending)} staleness={stale} "
              f"weights={np.round(fe._weights, 3).tolist()}")
        if fe.plane_outages:
            print(f"[serve] plane: outages={fe.plane_outages} "
                  f"dark-ticks={fe.plane_outage_ticks} "
                  f"local-actions={fe.local_actions_total}")
        if sup is not None:
            hs = sup.summary()
            print(f"[serve] hierarchy: plans={hs['plans']} "
                  f"local-actions={hs['local_actions']} "
                  f"(up={hs['local_up_actions']}) "
                  f"outage-steps={hs['outage_steps']} "
                  f"restores={hs['restores']} "
                  f"leases={hs['leases']}")
    if pool is not None:
        s = pool.summary()
        lm = s["latency_mean"]
        lp = s["latency_p95"]
        print(f"[serve] clients: n={s['clients']} issued={s['issued']} "
              f"ok={s['ok']} timed_out={s['timed_out']} "
              f"retries={s['retries']} abandoned={s['abandoned']} "
              f"rejected={s['rejected']} shed={s['shed']}"
              + (f" e2e mean={lm:.1f}t p95={lp:.1f}t"
                 if lm is not None else ""))
        for tname, row in sorted(s["per_tier"].items()):
            n_rids = max(row["ok"] + row["abandoned"], 1)
            print(f"[serve]   clients tier {tname:<10} "
                  f"goodput={row['ok']}/{n_rids} "
                  f"({row['ok'] / n_rids:.0%}) "
                  f"retries={row['retries']} abandoned={row['abandoned']}")
    return fe


def run_drain_mode(args, cfg, model, params):
    import jax.numpy as jnp

    from repro.data.pipeline import prompt_workload
    from repro.serving.engine import (ClusterFrontend, ReplicaEngine,
                                      Request, total_prefill_traces)

    if args.decode_block > 1:
        # the static ClusterFrontend steps each replica on its own; don't
        # let an A/B arm silently not differ
        print("[serve] note: --decode-block applies to the control-loop "
              "mode only; drain mode steps each replica on its own")

    replicas = [ReplicaEngine(model, params, max_batch=args.max_batch,
                              max_seq=args.max_seq, rid=i,
                              cache_dtype=jnp.dtype(cfg.dtype),
                              chunk_len=args.chunk_len,
                              attn_backend=args.attn_backend)
                for i in range(args.replicas)]
    caps = np.ones(args.replicas)

    def fractions_fn(fe):
        loads = np.asarray([r.load for r in fe.replicas], np.float64)
        w = caps / (1.0 + loads)
        return w / w.sum()

    fe = ClusterFrontend(replicas, policy=args.policy,
                         fractions_fn=fractions_fn, seed=args.seed)
    work = prompt_workload(cfg.vocab_size, args.requests, seed=args.seed)
    t0 = time.time()
    for w in work:
        fe.submit(Request(w["rid"], w["prompt"],
                          max_new_tokens=w["max_new_tokens"]))
    fe.run_until_drained()
    wall = time.time() - t0
    done = fe.finished
    toks = sum(len(r.output) for r in done)
    ttft = np.array([r.first_token_time for r in done])
    lat = np.array([r.finish_time for r in done])
    print(f"[serve] {len(done)}/{args.requests} finished, {toks} tokens in "
          f"{wall:.2f}s ({toks/wall:.1f} tok/s)")
    print(f"[serve] TTFT p50={np.percentile(ttft,50):.1f} "
          f"p95={np.percentile(ttft,95):.1f} engine-steps; "
          f"finish p50={np.percentile(lat,50):.1f} "
          f"p95={np.percentile(lat,95):.1f}")
    steps = sum(r.steps for r in replicas)
    traces = total_prefill_traces(replicas)
    print(f"[serve] decode steps across replicas: {steps} "
          f"(batch efficiency {toks/max(steps*args.max_batch,1):.2f}); "
          f"prefill retraces: {traces}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b-reduced",
                    help="config to serve: a published architecture, a "
                         "chip share such as 'granite-3-8b-1chip', or "
                         "'<either>-reduced' (the tiny CPU config); weights "
                         "and KV cache take the config's dtype")
    ap.add_argument("--policy", default="lc",
                    choices=["rr", "lc", "wrr", "fractions", "ours"])
    ap.add_argument("--autoscale", default=None,
                    choices=["none", "gpso", "ga", "hpa", "rbas", "static"])
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=1,
                    help="initial replicas per node (control mode) / total "
                         "replicas (drain mode)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--ticks", type=int, default=50)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean request arrivals per tick (control mode)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--provision-delay", type=int, default=3)
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--clients", type=int, default=0,
                    help="closed-loop client count; >0 replaces the "
                         "open-loop arrival trace with a ClientPool")
    ap.add_argument("--think-time", type=float, default=2.0,
                    help="mean client think time between requests (ticks)")
    ap.add_argument("--timeout", default="8",
                    help="per-attempt deadline in ticks: scalar ('8') or "
                         "per-tier dict ('premium:4,batch:16,default:8')")
    ap.add_argument("--retries", type=int, default=3,
                    help="max retries per request before a client abandons")
    ap.add_argument("--spawn-rate", type=float, default=None,
                    help="clients activated per tick (flash-crowd ramp); "
                         "default: all at once")
    ap.add_argument("--preempt-notice", type=int, default=3,
                    help="ticks of drain notice before a preempted node's "
                         "rows are dropped (spot semantics)")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault script, e.g. "
                         "'preempt@12:n0:k3,fail@8:n1:r0,recover@40:n0,"
                         "slow@5:n0:x4' (slow = straggler at 1/F speed "
                         "until 'x1' clears; multi-cell: node events land "
                         "on cell 0)")
    ap.add_argument("--cells", type=int, default=1,
                    help="federate N elastic cells behind the multi-cell "
                         "routing plane (control mode; 1 = single cell, "
                         "bit-identical to the direct frontend)")
    ap.add_argument("--cell-chaos", default="",
                    help="cell-level fault script for the routing plane, "
                         "e.g. 'cell_down@15:c0,partition@10:c1:k6,"
                         "cell_up@30:c0'; 'plane_down@10:k6'/'plane_up@20' "
                         "crash/restart the GLOBAL control plane")
    ap.add_argument("--hierarchy", action="store_true",
                    help="two-level control (needs --cells > 1): per-cell "
                         "reactive autoscalers inside GlobalPlanner "
                         "capacity leases under a crash-tolerant "
                         "PlaneSupervisor; the ControlPlane keeps "
                         "forecast+balance only")
    ap.add_argument("--plan-interval-global", type=int, default=10,
                    help="ticks between GlobalPlanner lease re-plans "
                         "(hierarchy mode)")
    ap.add_argument("--lease-slack", type=float, default=0.5,
                    help="lease headroom fraction above/below the planner "
                         "budget for local controllers to react into "
                         "(hierarchy mode)")
    ap.add_argument("--shed-threshold", type=float, default=0.0,
                    help="total-overload admission shedding: when every "
                         "healthy cell's tier pressure per unit capacity "
                         "exceeds this, shed lowest tiers first (0 = off; "
                         "multi-cell + tiers only)")
    ap.add_argument("--static-split", action="store_true",
                    help="disable adaptive cell routing (fixed uniform "
                         "split ignoring health/staleness/risk; the "
                         "multi-cell A/B baseline)")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="fuse K decode micro-steps into one dispatch+sync "
                         "on ticks that admit nothing (1 = one step per "
                         "tick; >1 trades <= K-1 ticks of admission lag "
                         "under a full slab)")
    ap.add_argument("--attn-backend", default="einsum",
                    choices=["einsum", "pallas"],
                    help="decode attention backend: dense einsum reference "
                         "or the Pallas flash-decode kernel (interpret mode "
                         "off-TPU)")
    ap.add_argument("--chunk-len", type=int, default=0,
                    help="chunked-prefill width: prompts longer than this "
                         "admit in fixed-size chunks interleaved with decode "
                         "(0 = single-shot prefill)")
    ap.add_argument("--tiers", default="",
                    help="SLO tier mix 'name:share:wWEIGHT[:ttft],...' e.g. "
                         "'premium:0.2:w5:4,standard:0.5:w2,batch:0.3:w1' — "
                         "share of traffic, weighted-deficit admission "
                         "weight, optional TTFT target in ticks (control "
                         "mode; default: single tier, identical to the "
                         "untiered scheduler)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard fleet slabs over an N-way ('fleet',) mesh; "
                         "on CPU exposes N virtual devices via XLA_FLAGS "
                         "(must run before jax initializes — this flag "
                         "handles the ordering; 0 = unsharded)")
    ap.add_argument("--mesh", default="",
                    help="explicit serving mesh spec 'SHAPE:AXES' (e.g. "
                         "'4:fleet') over already-visible devices; must "
                         "include a 'fleet' axis. Overrides --devices' "
                         "mesh shape but not its virtual-device setup")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    args = build_parser().parse_args()

    # device-count setup MUST precede the first jax import: XLA reads
    # --xla_force_host_platform_device_count once at backend init
    # (launch.mesh itself never imports jax at module level)
    from repro.launch.mesh import (make_fleet_mesh, parse_mesh_spec,
                                   set_host_device_count)

    if args.devices > 0:
        set_host_device_count(args.devices)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.models.model import make_model

    mesh = None
    if args.mesh:
        mesh = parse_mesh_spec(args.mesh)
    elif args.devices > 0:
        mesh = make_fleet_mesh(args.devices)
    if mesh is not None:
        print(f"[serve] mesh: {dict(zip(mesh.axis_names, mesh.shape.values()))}"
              f" over {len(mesh.devices.ravel())} device(s)")

    cfg = get_config(args.arch)
    model = make_model(cfg, tp=1)
    # under a mesh the weights are built replicated in place: no
    # single-device original stays alive beside the fleet's copy
    params = model.init(jax.random.PRNGKey(args.seed), cfg.dtype,
                        sharding=None if mesh is None
                        else NamedSharding(mesh, P()))
    print(f"[serve] arch={cfg.name} policy={args.policy}")

    # --cells/--hierarchy only exist in the control loop: requesting them
    # must not silently fall through to the legacy drain mode
    control_mode = (args.policy == "ours"
                    or (args.autoscale or "none") != "none"
                    or args.cells > 1 or args.hierarchy)
    if control_mode:
        if args.autoscale is None:
            args.autoscale = "gpso" if args.policy == "ours" else "none"
        run_control_loop(args, cfg, model, params, mesh=mesh)
    else:
        if mesh is not None:
            print("[serve] note: --devices/--mesh apply to the control-loop "
                  "mode only; drain mode steps replicas without a fleet slab")
        if args.policy == "wrr":
            args.policy = "fractions"
        run_drain_mode(args, cfg, model, params)


if __name__ == "__main__":
    main()
