"""Shared control-plane setup for the paper-figure benchmarks.

Trains the GRU forecaster + MADRL balancer once per process and caches the
trained state on disk (results/cache/) so the three figure benches and the
claims table share one controller.
"""
from __future__ import annotations

import os
import pickle

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.paper_cluster import ClusterConfig
from repro.core import balancer as bal
from repro.core import host_device
from repro.core.forecaster import train_forecaster
from repro.sim.experiment import run_episode, train_rl_balancer
from repro.sim.service_rate import replica_request_rate
from repro.workload import (LOAD_LEVELS, TraceConfig, generate_trace,
                            make_forecast_dataset)

CACHE_DIR = "results/cache"
CLUSTER = ClusterConfig(num_nodes=8)
SERVED_ARCH = "granite-3-8b"          # the model the cluster serves
UNIT_CAP = 30.0                       # req/s per replica (see service_rate)
TRAIN_LOAD = 1.8
BENCH_TICKS = 600
METHODS = ("RRA", "LCA", "HPA", "RBAS", "OURS")


def real_unit_capacity() -> float:
    """Roofline-derived req/s of one TP-16 replica serving SERVED_ARCH."""
    return replica_request_rate(get_config(SERVED_ARCH))


def _cache(name):
    os.makedirs(CACHE_DIR, exist_ok=True)
    return os.path.join(CACHE_DIR, name)


def get_controller(seed: int = 0, force: bool = False):
    """Returns (forecaster_params, rl_balancer). Cached on disk."""
    path = _cache("controller.pkl")
    rl = bal.RLBalancer(CLUSTER, 4 + CLUSTER.horizon, seed=seed)
    if os.path.exists(path) and not force:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        fp = blob["forecaster"]
        rl.state = jax.device_put(blob["ddpg"], host_device())
        return fp, rl
    ftrace = generate_trace(TraceConfig(ticks=2400), seed=97,
                            load_scale=TRAIN_LOAD)
    X, Y, _ = make_forecast_dataset(ftrace["arrivals"],
                                    CLUSTER.forecast_window, CLUSTER.horizon)
    fp, _ = train_forecaster(jax.random.PRNGKey(seed), X, Y,
                             CLUSTER.forecast_hidden, steps=400)
    traces = [generate_trace(TraceConfig(ticks=400), seed=s,
                             load_scale=TRAIN_LOAD) for s in range(3)]
    rl = train_rl_balancer(CLUSTER, traces, unit_capacity=UNIT_CAP,
                           episodes=6, forecaster_params=fp, seed=seed)
    with open(path, "wb") as f:
        pickle.dump({"forecaster": fp, "ddpg": rl.state}, f)
    return fp, rl


def run_method(method: str, load_scale: float, seed: int = 1,
               ticks: int = BENCH_TICKS, controller=None):
    trace = generate_trace(TraceConfig(ticks=ticks), seed=7,
                           load_scale=load_scale)
    kw = {}
    if method.startswith("OURS"):
        fp, rl = controller or get_controller()
        kw = {"rl": rl, "forecaster_params": fp}
    return run_episode(CLUSTER, trace, method, unit_capacity=UNIT_CAP,
                       seed=seed, **kw)
