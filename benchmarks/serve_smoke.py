"""Fast serving smoke for CI: tiny model, 2 replicas, hard asserts.

Guards the admission-path invariants cheap enough for every PR:

  * **fleet admission dispatch bound** — a cold burst of same-length
    prompts must admit in <= (distinct bucket shapes) jitted prefill
    dispatches per tick, never one per replica (here: equal lengths + equal
    group sizes -> exactly ONE shape -> ONE dispatch, vs 2 for the
    per-replica oracle);
  * **TTFT regression bound** — with chunked admission on, short requests
    sharing the cluster with near-``max_seq`` prompts must keep their TTFT
    p95 within the same small constant as a short-only run would give
    (admission is interleaved, not front-loaded);
  * **SLO tiers** — a 3-tier cold burst must (a) give premium a TTFT p95
    no worse than the untiered FIFO baseline on the identical workload,
    (b) still finish every batch-tier request (no starvation), and (c)
    keep the fleet dispatch bounds: tiering reorders which rows enter the
    one fleet prefill/decode per tick, it never adds dispatches;
  * **async tick contract** — on the same 3-tier config the (default)
    async tick must pay at most ONE blocking host sync per tick
    (``metrics()['syncs'] <= 1``, admissions included) and produce token
    streams bit-identical to the eager oracle; with ``decode_block=4`` the
    fused windows must engage (total syncs / ticks < 1);
  * **multi-cell chaos drill** — 2 elastic cells behind the fault-tolerant
    routing plane (``control.cells``) with a scripted ``cell_down`` under
    retrying clients: the single global ledger must balance with
    ``double_served == 0`` across the evacuation + re-route, and each cell
    must keep <= 1 sync and <= 1 decode dispatch per group per tick
    (churn-flush ticks excepted, same accounting as the chaos drill);
  * **plane-crash drill** — the same federation under the two-level
    hierarchy (``control.hierarchy``) with the GLOBAL plane crashed for 6
    ticks (``plane_down@4:k6``) while retrying clients ramp up: the
    per-cell controllers must keep taking scale actions inside their
    leases DURING the outage, the supervisor must reconcile exactly once
    on restore, the ledger must balance with ``double_served == 0``, and
    the per-cell sync/dispatch bounds must hold throughout;
  * **sharded fleet parity** — a child process with 4 virtual devices
    (``xla_force_host_platform_device_count=4``; the flag must precede
    jax's backend init, hence the subprocess) runs the same workload
    through a 4-way ``('fleet',)`` mesh and unsharded: token streams +
    finish clocks must match bit-for-bit and the sharded run must keep
    <= 1 blocking sync and one decode dispatch per group per tick.

Exits non-zero on violation (plain asserts); prints the measured numbers so
CI logs double as a mini-benchmark.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

MAX_SEQ = 64
MAX_BATCH = 4
CHUNK = 8
TTFT_P95_BOUND = 4.0     # ticks; generous vs the ~1-2 ticks measured


def main():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import make_model
    from repro.serving import ElasticClusterFrontend, ReplicaEngine, Request

    cfg = get_config("granite-3-8b").reduced()
    model = make_model(cfg, tp=1)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)

    # ---- fleet admission dispatch bound -------------------------------
    # 2 replicas x full batch of equal-length prompts: every replica
    # admits a (kb=4, sb=8) group -> ONE distinct bucket shape
    prompts = [rng.integers(1, cfg.vocab_size, 6).tolist()
               for _ in range(2 * MAX_BATCH)]

    def burst_fe(fp):
        def mk(rid):
            return ReplicaEngine(model, params, max_batch=MAX_BATCH,
                                 max_seq=MAX_SEQ, rid=rid)
        fe = ElasticClusterFrontend(mk, 1, initial_replicas=2,
                                    max_replicas_per_node=2, seed=0,
                                    fleet_prefill=fp)
        for i, p in enumerate(prompts):
            fe.submit(Request(i, list(p), max_new_tokens=3))
        return fe, fe.tick(0.0)

    fe_on, m_on = burst_fe(True)
    fe_off, m_off = burst_fe(False)
    distinct_shapes = 1
    print(f"[smoke] admission tick prefill_dispatches: "
          f"fleet={m_on['prefill_dispatches']} "
          f"per-replica={m_off['prefill_dispatches']} "
          f"(distinct bucket shapes={distinct_shapes})")
    assert m_on["prefill_dispatches"] <= distinct_shapes, \
        "fleet admission must cost <= one dispatch per distinct bucket shape"
    assert m_off["prefill_dispatches"] >= 2, \
        "per-replica oracle should pay one dispatch per admitting replica"
    fe_on.run_until_drained()
    fe_off.run_until_drained()
    snap = lambda fe: sorted((r.rid, tuple(r.output)) for r in fe.finished)
    assert snap(fe_on) == snap(fe_off), "fleet admission changed streams"

    # ---- chunked-admission TTFT bound ---------------------------------
    def mk_chunk(rid):
        return ReplicaEngine(model, params, max_batch=MAX_BATCH,
                             max_seq=MAX_SEQ, rid=rid, chunk_len=CHUNK)

    def rf(rid, tick):
        plen = MAX_SEQ - 2 if rid % 4 == 0 else int(rng.integers(4, 10))
        return Request(rid, rng.integers(1, cfg.vocab_size, plen).tolist(),
                       max_new_tokens=4)

    fe = ElasticClusterFrontend(mk_chunk, 1, initial_replicas=2,
                                max_replicas_per_node=2, request_factory=rf,
                                seed=0, est_tokens=4)
    for _ in range(30):
        fe.tick(1.0)
    fe.run_until_drained()
    short = [r for r in fe.finished if len(r.prompt) < MAX_SEQ - 2]
    ttft_p95 = float(np.percentile(
        [r.first_token_time - r.arrival for r in short], 95))
    print(f"[smoke] chunked run: {len(fe.finished)} requests, "
          f"short TTFT p95={ttft_p95:.1f} ticks (bound {TTFT_P95_BOUND})")
    assert ttft_p95 <= TTFT_P95_BOUND, \
        "chunked admission regressed short-request TTFT"

    # ---- 3-tier premium TTFT + dispatch bounds ------------------------
    from repro.workload import TierSet, TierSpec

    tiers = TierSet([TierSpec("premium", share=0.34, weight=5.0,
                              ttft_target=3.0),
                     TierSpec("standard", share=0.33, weight=2.0),
                     TierSpec("batch", share=0.33, weight=1.0)])
    burst = [rng.integers(1, cfg.vocab_size, 6).tolist() for _ in range(24)]

    def tier_burst(ts, async_tick=True, decode_block=1, n_new=3):
        def mk(rid):
            return ReplicaEngine(model, params, max_batch=MAX_BATCH,
                                 max_seq=MAX_SEQ, rid=rid, tiers=ts)
        fe = ElasticClusterFrontend(mk, 1, initial_replicas=2,
                                    max_replicas_per_node=2, seed=0,
                                    async_tick=async_tick,
                                    decode_block=decode_block, tiers=ts)
        for i, p in enumerate(burst):
            req = Request(i, list(p), max_new_tokens=n_new)
            if ts is not None:
                req.tier = tiers.names[i % 3]
            fe.submit(req)
        admit_m = fe.tick(0.0)
        max_decode, max_syncs, ticks = 0.0, admit_m["syncs"], 1
        for _ in range(200):
            m = fe.tick(0.0)
            ticks += 1
            if m["decode_dispatches"]:
                max_decode = max(max_decode, m["decode_dispatches"]
                                 / max(m["fleet_groups"], 1))
            if async_tick:
                max_syncs = max(max_syncs, m["syncs"])
            if not fe.pending and all(n.unfinished() == 0
                                      for n in fe.nodes):
                break
        return fe, admit_m, max_decode, max_syncs, ticks

    fe_t, admit_t, dec_t, sync_t, _ = tier_burst(tiers)
    fe_u, admit_u, _, _, _ = tier_burst(None)

    def ttft95(fe, pred):
        return float(np.percentile(
            [r.first_token_time - r.arrival
             for r in fe.finished if pred(r)], 95))

    prem = lambda r: r.rid % 3 == 0          # the same request population
    prem_tiered = ttft95(fe_t, prem)
    prem_untiered = ttft95(fe_u, prem)
    batch_done = [r for r in fe_t.finished if r.rid % 3 == 2]
    print(f"[smoke] 3-tier burst: premium TTFT p95 tiered={prem_tiered:.1f} "
          f"untiered={prem_untiered:.1f}; batch finished={len(batch_done)}/8; "
          f"admit prefill_dispatches={admit_t['prefill_dispatches']} "
          f"max decode_dispatches/group={dec_t:.1f}")
    assert prem_tiered <= prem_untiered, \
        "tiered premium TTFT p95 must not exceed the untiered baseline"
    assert len(batch_done) == 8, "batch tier starved under tiering"
    assert admit_t["prefill_dispatches"] <= 1, \
        "tiering must not add admission dispatches (one bucket shape)"
    assert admit_t["prefill_dispatches"] <= admit_u["prefill_dispatches"]
    assert dec_t <= 1.0, \
        "tiering must keep ONE fleet decode dispatch per group per tick"

    # ---- async tick: syncs_per_tick bound + eager stream parity -------
    assert sync_t <= 1, \
        "async tick must pay at most ONE blocking sync per tick"
    fe_e, _, _, _, _ = tier_burst(tiers, async_tick=False)
    snap_async = snap(fe_t)
    snap_eager = snap(fe_e)
    assert snap_async == snap_eager, \
        "async tick changed token streams vs the eager oracle"

    # decode_block=4: longer outputs so fused windows engage once the
    # admission wave passes; total syncs must amortize below 1/tick
    fe_b, _, _, _, ticks_b = tier_burst(tiers, decode_block=4, n_new=16)
    fe_r, _, _, _, _ = tier_burst(tiers, decode_block=1, n_new=16)
    spt = fe_b.sync_count() / ticks_b
    print(f"[smoke] async: max syncs/tick={sync_t} (streams == eager); "
          f"decode_block=4: syncs/tick={spt:.2f} over {ticks_b} ticks")
    assert spt < 1.0, "decode_block=4 must amortize syncs below 1/tick"
    # finish ticks may lag <= K-1 inside fused windows; token content is
    # the invariant
    toks_b = sorted((r.rid, tuple(r.output)) for r in fe_b.finished)
    toks_r = sorted((r.rid, tuple(r.output)) for r in fe_r.finished)
    assert toks_b == toks_r, "fused decode blocks changed token content"

    # ---- chaos drill: preemption + closed-loop clients ----------------
    # scripted spot preemption mid-load under retrying clients: the ledger
    # must balance (every rid exactly-once terminal, nothing served twice)
    # and the tick contract must hold through drain + hard drop
    from repro.serving import ChaosSchedule
    from repro.workload import ClientPool

    def mk_chaosrep(rid):
        return ReplicaEngine(model, params, max_batch=MAX_BATCH,
                             max_seq=MAX_SEQ, rid=rid)

    def cf(rid, tick):
        return Request(rid, rng.integers(1, cfg.vocab_size, 5).tolist(),
                       max_new_tokens=4)

    fe_c = ElasticClusterFrontend(
        mk_chaosrep, 2, initial_replicas=2, max_replicas_per_node=2,
        provisioning_delay=2, request_factory=cf, seed=0,
        preempt_notice=3,
        chaos=ChaosSchedule.parse("preempt@6:n0:k3,recover@14:n0"))
    pool = ClientPool(fe_c, 12, request_factory=cf, think_time=1.0,
                      timeout=6.0, max_retries=2, seed=1)
    max_syncs_c = max_disp_c = 0.0
    churn_over = steady_over = 0
    for _ in range(20):
        n_before = sum(len(n.live) + len(n.draining) for n in fe_c.nodes)
        pool.tick()
        m = fe_c.tick(0.0)
        n_after = sum(len(n.live) + len(n.draining) for n in fe_c.nodes)
        # steady-state contract: ONE reconcile sync per live fleet group
        # per tick. Membership churn (drain retire, preemption drop)
        # legitimately force-flushes that group's pending futures — one
        # extra sync on the tick a group's rows unstack, never more.
        over = m["syncs"] - max(m["fleet_groups"], 1)
        if over > 0:
            if n_after != n_before:
                churn_over += 1
                assert over <= 1, "churn tick paid more than one flush"
            else:
                steady_over += 1
        max_syncs_c = max(max_syncs_c, m["syncs"])
        if m["decode_dispatches"]:
            max_disp_c = max(max_disp_c, m["decode_dispatches"]
                             / max(m["fleet_groups"], 1))
    pool.quiesce()
    fe_c.run_until_drained()
    pool.finalize()
    led = fe_c.ledger
    s = pool.summary()
    print(f"[smoke] chaos drill: preempted_nodes={fe_c.preempted_nodes} "
          f"submitted={led.submitted} ok={s['ok']} retries={s['retries']} "
          f"abandoned={s['abandoned']} double_served={led.double_served} "
          f"max syncs/tick={max_syncs_c:.0f} "
          f"(churn flush ticks={churn_over}) "
          f"max decode_dispatches/group={max_disp_c:.1f}")
    assert fe_c.preempted_nodes >= 1, "scripted preemption did not fire"
    assert led.balanced(), f"ledger unbalanced under chaos: {led.balance()}"
    assert led.double_served == 0, "a request was served twice"
    assert s["ok"] > 0, "no goodput under the chaos drill"
    assert steady_over == 0, \
        "chaos broke the one-sync-per-group bound on a churn-free tick"
    assert max_disp_c <= 1.0, \
        "chaos broke the one-decode-dispatch-per-group bound"

    # ---- multi-cell chaos drill: cell blackout under the router -------
    # 2 elastic cells behind the routing plane, a scripted blackout while
    # retrying clients keep pressure on: the ONE global ledger must stay
    # balanced with nothing double-served across the evacuation + re-route,
    # and every cell must keep the per-tick sync/dispatch bounds (the
    # router adds zero device work of its own)
    from repro.control import MultiCellBackend

    def mc_cell(seed):
        return ElasticClusterFrontend(
            mk_chaosrep, 2, initial_replicas=1, max_replicas_per_node=2,
            provisioning_delay=2, seed=seed)

    mc = MultiCellBackend(
        [mc_cell(0), mc_cell(1)],
        chaos=ChaosSchedule.parse("cell_down@6:c0,cell_up@14:c0"), seed=0)
    pool_mc = ClientPool(mc, 12, request_factory=cf, think_time=1.0,
                         timeout=8.0, max_retries=2, seed=2)
    mc_churn = mc_steady = 0
    max_disp_mc = 0.0
    for _ in range(22):
        before = [sum(len(n.live) + len(n.draining) for n in cell.nodes)
                  for cell in mc.cells]
        pool_mc.tick()
        mc.tick(0.0)
        for cell, n_before in zip(mc.cells, before):
            m = cell.metrics()
            if not m:
                continue
            n_after = sum(len(n.live) + len(n.draining)
                          for n in cell.nodes)
            over = m["syncs"] - max(m["fleet_groups"], 1)
            if over > 0:
                if n_after != n_before:
                    mc_churn += 1      # churn flush: blackout/restore tick
                else:
                    mc_steady += 1
            if m["decode_dispatches"]:
                max_disp_mc = max(max_disp_mc, m["decode_dispatches"]
                                  / max(m["fleet_groups"], 1))
    pool_mc.quiesce()
    mc.run_until_drained()
    pool_mc.finalize()
    led_mc = mc.ledger
    s_mc = pool_mc.summary()
    print(f"[smoke] multi-cell drill: cell_downs={mc.cell_downs} "
          f"evacuated={mc.evacuated_total} submitted={led_mc.submitted} "
          f"ok={s_mc['ok']} retries={s_mc['retries']} "
          f"double_served={led_mc.double_served} "
          f"(churn flush ticks={mc_churn}) "
          f"max decode_dispatches/group/cell={max_disp_mc:.1f}")
    assert mc.cell_downs == 1, "scripted cell blackout did not fire"
    assert mc.evacuated_total > 0, "blackout caught no in-flight work"
    assert led_mc.balanced(), \
        f"global ledger unbalanced across cells: {led_mc.balance()}"
    assert led_mc.double_served == 0, \
        "a request was served twice across cells"
    assert s_mc["ok"] > 0, "no goodput through the multi-cell drill"
    assert mc_steady == 0, \
        "a cell broke the one-sync-per-group bound on a churn-free tick"
    assert max_disp_mc <= 1.0, \
        "a cell broke the one-decode-dispatch-per-group bound"

    # ---- plane-crash drill: two-level control through a global outage --
    # the hierarchy's fault-tolerance claim, asserted: with the global
    # plane dark for 6 ticks the per-cell controllers keep autoscaling
    # inside their last leases, the restored plane reconciles exactly
    # once, exactly-once accounting survives, and the device-work bounds
    # hold per cell
    from repro.control import (CellController, GlobalPlanner,
                               PlaneSupervisor)

    mc_h = MultiCellBackend(
        [mc_cell(0), mc_cell(1)],
        chaos=ChaosSchedule.parse("plane_down@4:k6"), seed=0)
    planner = GlobalPlanner(2, total_budget=4, max_per_cell=4,
                            lease_slack=0.5)
    ctls = [CellController(mc_h, c, patience=1, cooldown=1)
            for c in range(2)]
    sup = PlaneSupervisor(mc_h, planner, ctls, plan_interval=10)
    pool_h = ClientPool(mc_h, 12, request_factory=cf, think_time=1.0,
                        timeout=8.0, max_retries=2, spawn_rate=1.0, seed=3)
    h_steady = 0
    max_disp_h = max_stale = 0.0
    for _ in range(20):
        before = [sum(len(n.live) + len(n.draining) for n in cell.nodes)
                  for cell in mc_h.cells]
        pool_h.tick()
        m = sup.step(0.0)
        max_stale = max(max_stale, m["plane_staleness"])
        for cell, n_before in zip(mc_h.cells, before):
            cm = cell.metrics()
            if not cm:
                continue
            n_after = sum(len(n.live) + len(n.draining)
                          for n in cell.nodes)
            over = cm["syncs"] - max(cm["fleet_groups"], 1)
            if over > 0 and n_after == n_before:
                h_steady += 1
            if cm["decode_dispatches"]:
                max_disp_h = max(max_disp_h, cm["decode_dispatches"]
                                 / max(cm["fleet_groups"], 1))
    pool_h.quiesce()
    mc_h.run_until_drained()
    pool_h.finalize()
    led_h = mc_h.ledger
    s_h = pool_h.summary()
    dark = set(range(4, 10))
    dark_actions = sum(1 for ctl in ctls for t in ctl.action_ticks
                       if t in dark)
    print(f"[smoke] plane-crash drill: outages={mc_h.plane_outages} "
          f"dark-ticks={mc_h.plane_outage_ticks} "
          f"max plane_staleness={max_stale:.0f} "
          f"local-actions={sup.local_actions()} (in-outage={dark_actions}) "
          f"restores={sup.restores} plans={len(sup.plan_log)} "
          f"ok={s_h['ok']} double_served={led_h.double_served} "
          f"max decode_dispatches/group/cell={max_disp_h:.1f}")
    assert mc_h.plane_outages == 1 and mc_h.plane_outage_ticks == 6, \
        "scripted plane crash did not run its course"
    assert all(ctl.lease is not None for ctl in ctls), \
        "the planner never granted a lease"
    assert dark_actions > 0, \
        "cells must keep autoscaling while the global plane is dark"
    assert sup.restores == 1, "the restored plane must reconcile once"
    assert led_h.balanced(), \
        f"ledger unbalanced through the plane crash: {led_h.balance()}"
    assert led_h.double_served == 0, \
        "reconcile double-applied work after the plane restore"
    assert s_h["ok"] > 0, "no goodput through the plane-crash drill"
    assert h_steady == 0, \
        "a cell broke the one-sync-per-group bound on a churn-free tick"
    assert max_disp_h <= 1.0, \
        "a cell broke the one-decode-dispatch-per-group bound"

    # ---- sharded fleet parity (child process: 4 virtual devices) ------
    # a CPU parity check: the child is held to the CPU, since on a chip
    # host this (JAX-holding) parent already owns the accelerator
    env = dict(os.environ, SMOKE_SHARD_CHILD="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    child = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           capture_output=True, text=True, env=env,
                           timeout=600)
    sys.stdout.write(child.stdout)
    assert child.returncode == 0, \
        f"sharded smoke child failed:\n{child.stderr[-3000:]}"
    print("[smoke] OK")


def sharded_child():
    """Runs with 4 virtual devices (parent set XLA_FLAGS pre-spawn):
    sharded-vs-unsharded parity + the per-tick dispatch/sync bounds."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_fleet_mesh
    from repro.models import make_model
    from repro.serving import ElasticClusterFrontend, ReplicaEngine, Request

    assert jax.local_device_count() == 4, jax.local_device_count()
    mesh = make_fleet_mesh()
    cfg = get_config("granite-3-8b").reduced()
    model = make_model(cfg, tp=1)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, int(rng.integers(3, 9)))
               .tolist() for _ in range(12)]

    def run(use_mesh):
        def mk(rid):
            return ReplicaEngine(model, params, max_batch=MAX_BATCH,
                                 max_seq=MAX_SEQ, rid=rid)
        fe = ElasticClusterFrontend(mk, 2, initial_replicas=2, seed=0,
                                    mesh=mesh if use_mesh else None)
        for i, p in enumerate(prompts):
            fe.submit(Request(i, list(p), max_new_tokens=6))
        max_syncs = max_disp = 0
        for _ in range(200):
            m = fe.tick(0.0)
            max_syncs = max(max_syncs, m["syncs"])
            max_disp = max(max_disp, m["decode_dispatches"]
                           / max(m["fleet_groups"], 1))
            if not fe.pending and all(n.unfinished() == 0
                                      for n in fe.nodes):
                break
        fe.run_until_drained()
        streams = sorted((r.rid, tuple(r.output), r.finish_time)
                         for r in fe.finished)
        return streams, max_syncs, max_disp

    s_on, syncs_on, disp_on = run(True)
    s_off, _, _ = run(False)
    print(f"[smoke] sharded fleet ({jax.local_device_count()} devices): "
          f"max syncs/tick={syncs_on} "
          f"max decode_dispatches/group={disp_on:.1f}")
    assert s_on == s_off, "sharded fleet changed streams vs unsharded"
    assert syncs_on <= 1, "sharded tick must keep <= 1 blocking sync"
    assert disp_on <= 1.0, \
        "sharding must keep ONE logical decode dispatch per group per tick"


if __name__ == "__main__":
    if os.environ.get("SMOKE_SHARD_CHILD"):
        sharded_child()
    else:
        main()
