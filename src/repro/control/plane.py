"""``ControlPlane``: the paper's forecast -> balance -> scale loop, extracted
from the per-tick code previously duplicated across ``sim/experiment.py`` and
``examples/autoscale_sim.py``, and generalized over any ``ClusterBackend``.

Per tick (Eq.1-11):

    1. GRU demand forecast R̂_{t+1:t+T} over a rolling arrivals window
       (last-value persistence when no trained forecaster is given),
    2. balancer action a_t (MADRL GCN+DDPG, or the RRA/LCA/WRR baselines),
    3. backend advances one dt under a_t,
    4. RL reward/replay (optional training),
    5. autoscaling: GPSO replans every ``scale_interval`` ticks with
       volatility-aware headroom + an instantaneous-overload emergency path;
       the HPA/RBAS rule baselines observe every tick.

The same plane instance drives the fluid simulator (training, figures) and
the request-level elastic engine (``repro.launch.serve``) unchanged.

With the elastic backend's (default) overlapped async tick, step 3 returns
after ONE blocking host sync: the forecast -> balance -> scale work of this
loop runs while the accelerator computes the tick's decode
(``metrics()['sync_wait_s']`` is the engine's blocked time). The metrics the
plane observes then describe the device state as of one tick earlier —
scaling rules tolerate that lag by design (production autoscalers poll far
staler signals); the per-replica backend loop (``fleet_batch=False``)
observes synchronously when exact sim-parity of the control trajectory
matters. The plane's own computations (forecast, balancer, RL update,
GPSO/GA plan) run on ``core.host_device()``, the host's CPU, with every
input committed there: none joins the accelerator's queue, so their fetches
(through ``obs.timed_get``, accounted in ``sync_wait``/``syncs``) never
wait for the decode in flight, and the forecast and balance of the next
tick run while the accelerator still decodes this one. The frontend's
reconcile is then the one place the host waits for the decode. Each phase
of ``step`` is a ``repro.plane.*`` profiler span (``repro.obs``).

**Two-level hierarchy and crash tolerance** (PR 10, see
``control/hierarchy.py``): under ``PlaneSupervisor`` this plane is the
GLOBAL half of a two-level loop — it forecasts and balances, while
scaling authority is delegated as per-cell capacity leases
(``[min, max]`` total-replica bounds, enforced by the cell backends'
``set_lease``) that a ``GlobalPlanner`` re-grants every
``plan_interval`` ticks and per-cell ``CellController``s act inside at
full tick rate. The plane is crash-tolerant through
``state_dict``/``load_state_dict``: the checkpoint carries every piece
of mutable decision state (forecast window, residual tracker, learned
fractions, tick counter, scaler internals), so a restarted process that
loads it continues the exact decision stream. During an outage
(``plane_down@t`` chaos) ``step`` must not run — the supervisor ticks
the backend directly, cells keep scaling inside their LAST lease, and
the router rides the confidence-decayed capacity fallback; on
``plane_up`` the supervisor restores the checkpoint and re-plans leases
from live cell state instead of replaying pre-crash targets (no
double-applied scale actions).
"""
from __future__ import annotations

import copy
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import balancer as bal
from repro.core import host_device
from repro.core.autoscaler import (GPSOAutoscaler, HPAAutoscaler,
                                   RBASAutoscaler, StaticAllocator)
from repro.core.forecaster import forecast as nn_forecast
from repro.core.forecaster import last_value_baseline
from repro.obs import span, timed_get

# (balancer, autoscaler) pairs for the paper's §4.2 comparison matrix.
METHOD_SPECS = {
    "RRA": ("rr", "static"),
    "LCA": ("lc", "static"),
    "HPA": ("rr", "hpa"),
    "RBAS": ("rr", "rbas"),
    "OURS": ("rl", "gpso"),
    # extra references beyond the paper's table + ablations
    "WRR": ("wrr", "static"),
    "OURS-GA": ("rl", "ga"),     # GA-only autoscaler (no PSO refinement)
    "OURS-RR": ("rr", "gpso"),   # GPSO scaling but round-robin balancing
}

_jit_forecast = jax.jit(nn_forecast)


def make_autoscaler(kind: str, cfg, unit_cap: float, seed=0):
    if kind == "gpso":
        return GPSOAutoscaler(cfg, unit_cap, seed)
    if kind == "ga":
        return GPSOAutoscaler(cfg, unit_cap, seed, optimizer="ga")
    if kind == "hpa":
        return HPAAutoscaler(cfg)
    if kind == "rbas":
        return RBASAutoscaler(cfg)
    if kind == "static":
        return StaticAllocator(max(1, cfg.max_replicas_per_node // 2))
    if kind == "none":
        return None
    raise ValueError(kind)


class ControlPlane:
    """Composes forecaster + balancer + autoscaler over a ClusterBackend."""

    def __init__(self, cfg, backend, *, balancer: str = "rr",
                 scaler: str = "static", unit_capacity: float = 1.0,
                 rl: Optional[bal.RLBalancer] = None,
                 forecaster_params=None, forecast_scale: float = 1.0,
                 train_rl: bool = False, explore: bool = False,
                 train_every: int = 2, seed: int = 0,
                 init_arrival: float = 1.0):
        if balancer == "rl" and rl is None:
            raise ValueError("balancer='rl' needs an RLBalancer instance")
        self.cfg = cfg
        self.backend = backend
        self.balancer = balancer
        self.rl = rl
        self.host = host_device()
        self.forecaster_params = None if forecaster_params is None else \
            jax.device_put(forecaster_params, self.host)
        self.forecast_scale = float(forecast_scale)
        self.train_rl = train_rl
        self.explore = explore
        self.train_every = train_every
        self.unit_capacity = unit_capacity
        self.scaler_kind = scaler
        self.scaler = make_autoscaler(scaler, cfg, unit_capacity, seed)
        n = backend.num_nodes
        self.t = 0
        self.window = np.full((cfg.forecast_window,), float(init_arrival),
                              np.float32)
        self.fractions = np.full((n,), 1.0 / n, np.float32)
        self._prev = None            # (obs, action, reward) for RL replay
        self._resid = np.zeros(64, np.float32)   # rolling forecast residuals
        self._prev_fc1 = None
        # blocking device fetches of the plane's own work (forecast, actor,
        # GPSO plan), accounted as the engine accounts its own
        self.syncs = 0
        self.sync_wait = 0.0

    # -------------------------------------------------- checkpoint/restore
    def state_dict(self) -> dict:
        """Deep-copied snapshot of every piece of mutable decision state —
        loading it into a FRESH plane over the same backend continues the
        exact decision stream (asserted in ``tests/test_hierarchy.py``).
        The RL replay tuple is transient (one tick of context) and resets
        on restore; the rl balancer itself is externally owned."""
        return {
            "t": int(self.t),
            "window": self.window.copy(),
            "fractions": self.fractions.copy(),
            "resid": self._resid.copy(),
            "prev_fc1": self._prev_fc1,
            "scaler": copy.deepcopy(self.scaler),
        }

    def load_state_dict(self, state: dict) -> None:
        self.t = int(state["t"])
        self.window = state["window"].copy()
        self.fractions = state["fractions"].copy()
        self._resid = state["resid"].copy()
        self._prev_fc1 = state["prev_fc1"]
        self.scaler = copy.deepcopy(state["scaler"])
        self._prev = None

    # ------------------------------------------------------------ forecast
    def _forecast(self, arrival_rate: float) -> np.ndarray:
        window = jnp.asarray(self.window[:, None] / self.forecast_scale,
                             device=self.host)
        if self.forecaster_params is not None:
            fc = _jit_forecast(self.forecaster_params, window)
        else:
            fc = last_value_baseline(window, self.cfg.horizon)
        fc = np.asarray(timed_get(self, fc))[:, 0]
        fc = fc.astype(np.float32)
        # rolling 1-step forecast-error tracker -> volatility-aware headroom
        if self._prev_fc1 is not None:
            self._resid = np.roll(self._resid, -1)
            self._resid[-1] = (arrival_rate / self.forecast_scale
                               - self._prev_fc1)
        self._prev_fc1 = float(fc[0])
        return fc

    # ------------------------------------------------------------- balance
    def _balance(self, obs, up, arrival_rate: float) -> np.ndarray:
        b = self.backend
        obs, up = jax.device_put((obs, up), self.host)
        if self.balancer == "rr":
            fr = bal.round_robin(obs, up)
        elif self.balancer == "lc":
            queue, mass = jax.device_put(
                (b.queue_depths(),
                 np.float32(arrival_rate * self.cfg.tick_seconds)),
                self.host)
            fr = bal.least_connections(queue, up, mass)
        elif self.balancer == "wrr":
            fr = bal.weighted_capacity(
                obs, up, jax.device_put(b.capacity(), self.host))
        elif self.balancer == "rl":
            fr = self.rl.act(obs, up, explore=self.explore)
        else:
            raise ValueError(self.balancer)
        return np.asarray(timed_get(self, fr))

    # --------------------------------------------------------------- scale
    def _scale(self, m: dict, fc: np.ndarray, arrival_rate: float):
        cfg = self.cfg
        in_flight = self.backend.in_flight()
        if self.scaler_kind in ("gpso", "ga"):
            # measured service rates: once the backend's finished-request EMA
            # is warm (``service_rate`` per live replica), the planner uses
            # it instead of the static unit_capacity guess — closing the loop
            # on replica throughput. Backends that don't measure (the fluid
            # sim) simply never emit the key and keep the constant.
            measured = m.get("service_rate")
            if measured:
                self.scaler.unit_capacity = float(measured)
            if self.t % cfg.scale_interval == 0 and self.t > 0:
                # provision for the P95 of predicted demand: forecast peak
                # plus 2 sigma of recent forecast error, so calm periods run
                # lean and bursty ones hold reserve.
                n = self.backend.num_nodes
                sigma = float(self._resid.std()) * self.forecast_scale
                peak = max(float(fc.max()) * self.forecast_scale,
                           float(arrival_rate)) + 2.0 * sigma
                node_demand = peak * np.maximum(self.fractions,
                                                1.0 / (4 * n))
                # tiered backends report a weighted per-node backlog; the
                # plan then optimizes Eq.9 + the SLO-violation cost term.
                # chaos-aware backends report per-node preemption risk; any
                # nonzero risk adds the Eq.9 spot-churn cost term
                target = self.scaler.plan(node_demand, self.t, in_flight,
                                          node_speed=self.backend.node_speed,
                                          slo_pressure=m.get("tier_pressure"),
                                          preempt_risk=m.get("preempt_risk"),
                                          owner=self)
                self.backend.scale_to(target)
            else:
                # emergency path: instantaneous overload on a node triggers
                # an immediate scale-up without waiting for the plan interval
                hot = m["utilization"] > 0.95
                if hot.any():
                    target = in_flight + hot.astype(np.int32)
                    self.backend.scale_to(
                        np.minimum(target, cfg.max_replicas_per_node))
        elif self.scaler is not None and self.scaler_kind != "static":
            # rule-based scalers observe every tick (the k8s control loop)
            target = self.scaler.plan(m["utilization"], self.t, in_flight)
            self.backend.scale_to(target)
        # "static"/"none": the backend keeps its initial replica profile

    # ---------------------------------------------------------------- tick
    def step(self, arrival_rate: float) -> dict:
        """One forecast -> balance -> advance -> (learn) -> scale tick."""
        with span("plane.step"):
            return self._step(arrival_rate)

    def _step(self, arrival_rate: float) -> dict:
        cfg = self.cfg
        with span("plane.forecast"):
            fc = self._forecast(arrival_rate)
        with span("plane.observe"):
            obs = self.backend.observe(fc)
            up = self.backend.up_mask()
        with span("plane.balance"):
            self.fractions = self._balance(obs, up, arrival_rate)
            self.backend.route(self.fractions)
        m = self.backend.tick(arrival_rate)

        if self.balancer == "rl":
            with span("plane.learn"):
                # Eq.5, tier-weighted: backends serving tiered traffic
                # report a weighted SLO violation level; untiered backends
                # omit the key and the reward reduces to the original shape.
                reward = bal.reward_fn(
                    m["response_time"], m["mean_utilization"], cfg.alpha,
                    cfg.beta, m["overload"], slo_cost=cfg.slo_gamma *
                    float(m.get("tier_slo_cost") or 0.0))
                if self._prev is not None and self.train_rl:
                    self.rl.observe(self._prev[0], self._prev[1],
                                    float(self._prev[2]), obs, up)
                    if self.t % self.train_every == 0:
                        self.rl.train_step()
                self._prev = (obs, self.fractions, reward)

        with span("plane.scale"):
            self._scale(m, fc, arrival_rate)

        self.window = np.roll(self.window, -1)
        self.window[-1] = arrival_rate
        self.t += 1
        return m

    def run(self, arrivals: np.ndarray) -> list:
        """Drive a whole trace; returns the per-tick metrics dicts."""
        return [self.step(float(a)) for a in arrivals]
