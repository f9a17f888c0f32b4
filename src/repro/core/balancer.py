"""Load balancers: the paper's MADRL(GCN+DDPG) policy + the §4.2 baselines.

Every balancer maps per-tick cluster observations to a simplex allocation
a_t over nodes (Eq. 4): fractions of the tick's request mass per node. In the
fluid cluster simulator this is exact; in the request-level serving engine
the fractions drive per-request routing.

Baselines (paper §4.2): RRA (round robin -> uniform over healthy nodes),
LCA (least connections -> water-filling on queue depth, capacity-blind),
plus WRR (capacity-weighted) as an extra reference.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ddpg, host_device
from repro.core.gcn import make_topology, normalize_adjacency


def _mask_normalize(w, up_mask):
    w = jnp.where(up_mask > 0, w, 0.0)
    s = jnp.sum(w, axis=-1, keepdims=True)
    n_up = jnp.sum(up_mask, axis=-1, keepdims=True)
    uniform = up_mask / jnp.maximum(n_up, 1.0)
    return jnp.where(s > 1e-9, w / jnp.maximum(s, 1e-9), uniform)


def round_robin(obs, up_mask):
    """RRA: uniform over healthy nodes (per-request RR in the fluid limit)."""
    return _mask_normalize(jnp.ones_like(up_mask), up_mask)


def weighted_capacity(obs, up_mask, capacity):
    """WRR: fractions ∝ node capacity."""
    return _mask_normalize(capacity, up_mask)


def least_connections(queue, up_mask, total_arrivals):
    """LCA as water-filling: route this tick's arrivals so post-routing queue
    depths equalize from the bottom up (what per-request least-connections
    converges to within a tick). Capacity-blind, like the real algorithm.

    queue: (N,) outstanding work; total_arrivals: scalar mass to place.
    """
    N = queue.shape[-1]
    big = 1e18
    q = jnp.where(up_mask > 0, queue, big)
    order = jnp.argsort(q)
    qs = q[order]
    # find water level L: sum_i max(0, L - q_i) = total => for first k nodes
    csum = jnp.cumsum(qs)
    k = jnp.arange(1, N + 1)
    level = (csum + total_arrivals) / k            # candidate level using k lowest
    next_q = jnp.concatenate([qs[1:], jnp.full((1,), big)])
    feasible = (level >= qs) & (level <= next_q)
    k_star = jnp.argmax(feasible)                  # first feasible k
    L = level[k_star]
    alloc_sorted = jnp.clip(L - qs, 0.0, None) * (jnp.arange(N) <= k_star)
    alloc = jnp.zeros_like(q).at[order].set(alloc_sorted)
    alloc = jnp.where(up_mask > 0, alloc, 0.0)
    s = jnp.sum(alloc)
    return jnp.where(s > 1e-9, alloc / jnp.maximum(s, 1e-9),
                     _mask_normalize(jnp.ones_like(q), up_mask))


@dataclasses.dataclass
class RLBalancer:
    """The paper's balancer: GCN+DDPG actor producing A_t from S_t.

    The actor's state and the adjacency are committed to ``host_device()``
    when built, so acting and the DDPG update run there."""
    cluster_cfg: "ClusterConfig"
    feat_dim: int
    seed: int = 0

    def __post_init__(self):
        cfg = self.cluster_cfg
        host = host_device()
        self.a_hat = jnp.asarray(normalize_adjacency(
            make_topology(cfg.num_nodes, cfg.topology)), device=host)
        key = jax.device_put(jax.random.PRNGKey(self.seed), host)
        self.state = jax.device_put(
            ddpg.init_ddpg(key, self.feat_dim, cfg), host)
        self.buffer = ddpg.ReplayBuffer(cfg.buffer_size, cfg.num_nodes,
                                        self.feat_dim)
        self._rng = np.random.default_rng(self.seed)
        self._act = jax.jit(ddpg.actor_action)

    # -- acting ---------------------------------------------------------
    def act(self, obs, up_mask, explore: bool = False):
        noise = None
        if explore:
            noise = jnp.asarray(self._rng.normal(
                0.0, self.cluster_cfg.noise_sigma, obs.shape[:-1]),
                device=host_device())
        return self._act(self.state.actor, self.a_hat, obs,
                         up_mask=up_mask, noise=noise)

    # -- learning -------------------------------------------------------
    def observe(self, obs, action, reward, next_obs, up_mask):
        self.buffer.add(np.asarray(obs), np.asarray(action), float(reward),
                        np.asarray(next_obs), np.asarray(up_mask))

    def train_step(self):
        cfg = self.cluster_cfg
        if self.buffer.size < cfg.batch_size:
            return {}
        batch = self.buffer.sample(self._rng, cfg.batch_size)
        tup = (self.state.actor, self.state.critic,
               self.state.actor_target, self.state.critic_target)
        tup, metrics = ddpg.ddpg_update(
            tup, self.a_hat, batch, gamma=cfg.gamma, tau=cfg.tau,
            actor_lr=cfg.actor_lr, critic_lr=cfg.critic_lr)
        self.state = ddpg.DDPGState(*tup)
        return {k: float(v) for k, v in metrics.items()}


def reward_fn(response_time, utilization, alpha, beta, overload,
              slo_cost: float = 0.0):
    """Eq.5 (see DESIGN.md §8 for the utilization-term interpretation):
    R_t = -(α·ResponseTime + β·(idle-capacity + overload penalty)
            + tier-weighted SLO cost).

    Response time enters through log1p so transient queue blow-ups cannot
    destabilize the critic (reward stays O(1)). ``slo_cost`` is the
    tier-weighted SLO violation level of the tick (already scaled by the
    caller, e.g. ``cfg.slo_gamma * metrics['tier_slo_cost']``): with tiered
    traffic the policy is penalized more for premium-tier misses than for
    batch-tier ones; untiered runs pass 0 and recover the original Eq.5."""
    idle_cost = 1.0 - utilization
    rt_cost = float(np.log1p(response_time))
    return -(alpha * rt_cost + beta * (idle_cost + 2.0 * overload)
             + float(slo_cost))
