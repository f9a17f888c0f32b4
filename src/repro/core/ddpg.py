"""GCN+DDPG hybrid policy for adaptive load distribution (paper §3.1).

Actor: node features --GCN(Eq.6)--> per-node embeddings --shared MLP-->
per-node logits --softmax--> simplex allocation A_t (Eq.4/7). The shared
per-node head IS the paper's "shared policy network with local information
fusion": every agent (node) runs the same head on its GCN-fused local view.

Critic: Q(S_t, A_t) — GCN embeddings concat per-node action, shared MLP,
summed over nodes (permutation-equivariant, so the same critic serves any
cluster size). Trained on the TD target (Eq.8) with target networks and a
replay buffer; soft (polyak) target updates.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gcn import gcn_apply, init_gcn
from repro.models.layers import he_init


def init_mlp_head(key, in_dim, hidden, out_dim, final_scale=1.0):
    k1, k2 = jax.random.split(key)
    return {
        "w1": he_init(k1, (in_dim, hidden), jnp.float32),
        "b1": jnp.zeros((hidden,)),
        "w2": he_init(k2, (hidden, out_dim), jnp.float32) * final_scale,
        "b2": jnp.zeros((out_dim,)),
    }


def mlp_head(p, x):
    return jax.nn.relu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def init_actor(key, feat_dim, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "gcn": init_gcn(k1, feat_dim, cfg.gcn_hidden, cfg.gcn_layers),
        "head": init_mlp_head(k2, cfg.gcn_hidden + feat_dim,
                              cfg.actor_hidden, 1, final_scale=0.01),
    }


def init_critic(key, feat_dim, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "gcn": init_gcn(k1, feat_dim, cfg.gcn_hidden, cfg.gcn_layers),
        "head": init_mlp_head(k2, cfg.gcn_hidden + feat_dim + 1,
                              cfg.critic_hidden, 1),
    }


def actor_logits(params, a_hat, obs):
    """obs: (..., N, F) -> per-node logits (..., N)."""
    h = gcn_apply(params["gcn"], a_hat, obs)
    h = jnp.concatenate([h, obs], axis=-1)     # local skip (info fusion)
    return mlp_head(params["head"], h)[..., 0]


def actor_action(params, a_hat, obs, up_mask=None, noise=None):
    """Simplex allocation over nodes (Eq.4). Noise (Eq.7) added to logits.

    up_mask: (..., N) 1 for healthy nodes — failed nodes get zero traffic
    (the decentralized fault-tolerance hook).
    """
    logits = actor_logits(params, a_hat, obs)
    if noise is not None:
        logits = logits + noise
    if up_mask is not None:
        logits = jnp.where(up_mask > 0, logits, -1e9)
    return jax.nn.softmax(logits, axis=-1)


def critic_q(params, a_hat, obs, action):
    """Q(S_t, A_t): (..., N, F), (..., N) -> (...)."""
    h = gcn_apply(params["gcn"], a_hat, obs)
    h = jnp.concatenate([h, obs, action[..., None]], axis=-1)
    q = mlp_head(params["head"], h)[..., 0]    # per-node q contribution
    return jnp.sum(q, axis=-1)


# ------------------------------------------------------------------ training
@dataclasses.dataclass
class ReplayBuffer:
    """Numpy ring buffer of (obs, action, reward, next_obs, up_mask)."""
    capacity: int
    n_nodes: int
    feat_dim: int

    def __post_init__(self):
        C, N, F = self.capacity, self.n_nodes, self.feat_dim
        self.obs = np.zeros((C, N, F), np.float32)
        self.act = np.zeros((C, N), np.float32)
        self.rew = np.zeros((C,), np.float32)
        self.nxt = np.zeros((C, N, F), np.float32)
        self.mask = np.ones((C, N), np.float32)
        self.size = 0
        self.ptr = 0

    def add(self, obs, act, rew, nxt, mask):
        i = self.ptr
        self.obs[i], self.act[i], self.rew[i] = obs, act, rew
        self.nxt[i], self.mask[i] = nxt, mask
        self.ptr = (i + 1) % self.capacity
        self.size = min(self.size + 1, self.capacity)

    def sample(self, rng: np.random.Generator, batch: int):
        idx = rng.integers(0, self.size, size=batch)
        return (self.obs[idx], self.act[idx], self.rew[idx], self.nxt[idx],
                self.mask[idx])


def polyak(target, online, tau):
    return jax.tree.map(lambda t, o: (1 - tau) * t + tau * o, target, online)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DDPGState:
    actor: dict
    critic: dict
    actor_target: dict
    critic_target: dict


def init_ddpg(key, feat_dim, cfg) -> DDPGState:
    k1, k2 = jax.random.split(key)
    actor = init_actor(k1, feat_dim, cfg)
    critic = init_critic(k2, feat_dim, cfg)
    return DDPGState(actor, critic,
                     jax.tree.map(jnp.copy, actor),
                     jax.tree.map(jnp.copy, critic))


@functools.partial(jax.jit, static_argnames=("gamma", "tau", "actor_lr",
                                             "critic_lr"))
def ddpg_update(state_tuple, a_hat, batch, *, gamma, tau, actor_lr, critic_lr):
    """One TD + policy-gradient step (Eq.8). state_tuple = (actor, critic,
    actor_t, critic_t); batch = (obs, act, rew, nxt, mask)."""
    actor, critic, actor_t, critic_t = state_tuple
    obs, act, rew, nxt, mask = batch

    def clip_by_norm(grads, max_norm=1.0):
        g2 = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(jnp.sqrt(g2), 1e-9))
        return jax.tree.map(lambda g: g * scale, grads)

    next_a = actor_action(actor_t, a_hat, nxt, up_mask=mask)
    target_q = rew + gamma * critic_q(critic_t, a_hat, nxt, next_a)
    target_q = jax.lax.stop_gradient(target_q)

    def critic_loss(c):
        q = critic_q(c, a_hat, obs, act)
        return jnp.mean(jnp.square(q - target_q))

    c_loss, c_grads = jax.value_and_grad(critic_loss)(critic)
    c_grads = clip_by_norm(c_grads)
    critic = jax.tree.map(lambda p, g: p - critic_lr * g, critic, c_grads)

    def actor_loss(a):
        action = actor_action(a, a_hat, obs, up_mask=mask)
        return -jnp.mean(critic_q(critic, a_hat, obs, action))

    a_loss, a_grads = jax.value_and_grad(actor_loss)(actor)
    a_grads = clip_by_norm(a_grads)
    actor = jax.tree.map(lambda p, g: p - actor_lr * g, actor, a_grads)

    actor_t = polyak(actor_t, actor, tau)
    critic_t = polyak(critic_t, critic, tau)
    return (actor, critic, actor_t, critic_t), {"critic_loss": c_loss,
                                                "actor_loss": a_loss}
