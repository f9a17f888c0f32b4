"""Sharding rules: param-path → PartitionSpec, activation constraints, and
input shardings for every (arch × shape-kind).

Two regimes (DESIGN.md §4):
  train — FSDP over ("pod","data") on each tensor's non-TP dim + TP over
          "model" (heads / d_ff / vocab). Optimizer moments follow weights.
  serve — weights replicated over data axes, TP over "model"; KV caches
          shard batch over data and kv-heads over "model".

Rules are written against *trailing* dims so stacked-layer leading axes
(L, groups, ...) are automatically replicated. Any dim whose size does not
divide its mesh axes falls back to replication (e.g. batch=1 long_500k).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    mesh: Mesh
    mode: str                      # "train" | "serve"
    expert_sharding: str = "none"  # "none" | "data" (EP)

    @property
    def dp_axes(self):
        """Data-like axes (batch + FSDP). A dedicated 'expert' axis (the
        EP mesh refactor, e.g. (data=2, expert=8, model=16)) still carries
        batch/FSDP for the non-MoE tensors."""
        names = self.mesh.axis_names
        return tuple(a for a in ("pod", "data", "expert") if a in names)

    @property
    def ep_axis(self):
        """Axis holding the expert dim: an explicit 'expert' mesh axis, or
        the data axes when expert_sharding='data'."""
        if "expert" in self.mesh.axis_names:
            return ("expert",)
        if self.expert_sharding == "data":
            return self.dp_axes
        return None

    @property
    def expert_inner_axes(self):
        """Data axes usable for the within-expert dims (excludes ep_axis)."""
        ep = self.ep_axis or ()
        return tuple(a for a in self.dp_axes if a not in ep) or None

    @property
    def tp_axis(self):
        return "model" if "model" in self.mesh.axis_names else None

    @property
    def fsdp(self):
        """Weight-sharding data axes (None in serve mode -> replicated)."""
        return self.dp_axes if self.mode == "train" else None

    @property
    def tp_size(self) -> int:
        return self.mesh.shape.get("model", 1)


# --------------------------------------------------------------- param rules
def _trailing_rules(plan: ShardPlan, path_names: tuple) -> Optional[tuple]:
    """Spec for the trailing dims of a param, by leaf name (+ context)."""
    name = path_names[-1]
    in_moe = "moe" in path_names or "moe_layers" in path_names
    fsdp, tp = plan.fsdp, plan.tp_axis
    ep = plan.ep_axis if in_moe else None
    # MoE expert weights are ~95% of a MoE model's params: keep them
    # data-sharded even in serve mode (TP alone cannot hold 300-400B weights
    # in 16 GB/chip; the per-layer gather is one expert block, not the model).
    # Under EP the expert dim takes its own axis; within-expert dims use the
    # remaining data axes.
    moe_fsdp = plan.expert_inner_axes if ep else \
        (plan.dp_axes if in_moe else fsdp)
    table = {
        "embed": (tp, fsdp),            # (V, d)
        "lm_head": (fsdp, tp),          # (d, V)
        "patch_proj": (fsdp, tp),       # (d, d)
        "dec_pos": (None, fsdp),        # (S, d)
        "wq": (fsdp, tp, None),         # (d, nq, hd)
        "wk": (fsdp, tp, None),
        "wv": (fsdp, tp, None),
        "wq_served": (tp, None, None, fsdp),  # (G, qpg, hd, d)
        "wk_served": (tp, None, fsdp),  # (G, hd, d)
        "wv_served": (tp, None, fsdp),
        "wo": (tp, None, fsdp),         # (nq, hd, d)
        "bq": (tp, None),
        "bk": (tp, None),
        "bv": (tp, None),
        "router": (fsdp, None),         # (d, E)
        "in_proj": (fsdp, None),        # (d, d_in_proj) — see DESIGN §4
        "out_proj": (tp, fsdp),         # (d_inner, d)
        "conv_w": (None, tp),           # (W, C)
        "conv_b": (tp,),
        "norm_scale": (tp,),            # (d_inner,)
        "head": (fsdp, None),
    }
    if name in ("w_gate", "w_up"):
        if in_moe and len(path_names) >= 2 and path_names[-2] != "shared":
            return (ep[0] if ep else None, moe_fsdp, tp)   # (E, d, ff)
        return (fsdp, tp)                                  # (d, ff)
    if name == "w_down":
        if in_moe and len(path_names) >= 2 and path_names[-2] != "shared":
            return (ep[0] if ep else None, tp, moe_fsdp)   # (E, ff, d)
        return (tp, fsdp)
    return table.get(name)


def _fits(spec_entry, dim: int, mesh: Mesh) -> bool:
    if spec_entry is None:
        return True
    axes = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return dim % size == 0


def param_pspec(plan: ShardPlan, path, leaf) -> P:
    names = tuple(
        p.key if hasattr(p, "key") else str(p) for p in path)
    right = _trailing_rules(plan, names)
    ndim = leaf.ndim
    if right is None or ndim < len(right):
        return P()
    lead = (None,) * (ndim - len(right))
    entries = []
    for e, dim in zip(lead + tuple(right), leaf.shape):
        entries.append(e if _fits(e, dim, plan.mesh) else None)
    return P(*entries)


def param_shardings(plan: ShardPlan, params):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(plan.mesh,
                                         param_pspec(plan, path, leaf)),
        params)


# ----------------------------------------------------------- activation tags
def make_shard_fn(plan: ShardPlan):
    """shard_fn(x, tag) used inside model code (GSPMD constraint hints)."""
    dp, tp = plan.dp_axes, plan.tp_axis
    # (E, B, C, d) dispatch buffer: E must follow the expert-weight sharding
    # (EP: E over the expert axes, batch over the rest) or GSPMD re-gathers
    # the expert weights to match the buffer.
    if plan.ep_axis:
        moe_buf = (plan.ep_axis, plan.expert_inner_axes, None, None)
    else:
        moe_buf = (None, dp, None, None)
    specs = {
        "act_btd": (dp, None, None),
        "logits": (dp, None, tp),
        "qkv": (dp, None, tp, None, None),
        "kv": (dp, None, tp, None),
        "moe_buf": moe_buf,
    }

    def shard_fn(x, tag):
        spec = specs.get(tag)
        if spec is None or x.ndim != len(spec):
            return x
        entries = [e if _fits(e, d, plan.mesh) else None
                   for e, d in zip(spec, x.shape)]
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(plan.mesh, P(*entries)))

    return shard_fn


# --------------------------------------------------------------- input specs
def batch_shardings(plan: ShardPlan, batch_specs):
    """Shardings for train/prefill inputs: batch dim over data axes."""
    dp = plan.dp_axes

    def one(spec):
        entries = [dp if _fits(dp, spec.shape[0], plan.mesh) else None]
        entries += [None] * (len(spec.shape) - 1)
        return NamedSharding(plan.mesh, P(*entries))

    return jax.tree.map(one, batch_specs)


def _serve_state_entries(name: str, ndim: int, dp, tp) -> tuple:
    """Per-dim axis entries for one serve-state leaf (batch over data, heads
    over model) — shared by the per-replica and fleet-slab rule sets.

    Leaf layouts (leading stack axis first):
      lm k/v            (L, B, S, G, hd)
      ssm 'ssm'         (L, B, H, P, N)
      ssm 'conv'        (L, B, W-1, C)
      hybrid attn_k/v   (n_inv, B, S, G, hd)
      encdec self/cross (L, B, S, G, hd)
    """
    if name in ("k", "v", "attn_k", "attn_v", "self_k", "self_v",
                "cross_k", "cross_v"):
        return (None, dp, None, tp, None)
    if name == "ssm":
        return (None, dp, tp, None, None)
    if name == "conv":
        return (None, dp, None, tp)
    return (None,) * ndim


def serve_state_shardings(plan: ShardPlan, state_specs, cfg):
    """Decode-state shardings: batch over data, heads over model (see
    ``_serve_state_entries`` for the leaf layouts)."""
    dp, tp = plan.dp_axes, plan.tp_axis

    def one(path, spec):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape = spec.shape
        entries = _serve_state_entries(name, len(shape), dp, tp)
        entries = [e if _fits(e, d, plan.mesh) else None
                   for e, d in zip(entries, shape)]
        return NamedSharding(plan.mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(one, state_specs)


def fleet_slab_shardings(mesh: Mesh, slab_specs):
    """Shardings for a ``FleetGroup`` slab: the leading fleet axis maps over
    the mesh's ``fleet`` axis (F replicas decode on N devices in parallel);
    trailing per-replica dims reuse the serve-mode rules on any data/model
    axes also present (a pure ``('fleet',)`` serving mesh replicates them).
    Params are NOT sharded this way — they replicate over the fleet axis
    (every shard decodes its own slab rows with the full weights). A leading
    dim that does not divide the fleet axis falls back to replication, so
    callers must keep slab capacity a multiple of the shard count (see
    ``FleetGroup`` growth)."""
    if "fleet" not in mesh.axis_names:
        raise ValueError(
            f"serving mesh needs a 'fleet' axis, got {mesh.axis_names}")
    dp = tuple(a for a in ("pod", "data", "expert") if a in mesh.axis_names)
    dp = dp or None
    tp = "model" if "model" in mesh.axis_names else None

    def one(path, spec):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape = spec.shape
        entries = ("fleet",) + _serve_state_entries(name, len(shape) - 1,
                                                    dp, tp)
        entries = [e if _fits(e, d, mesh) else None
                   for e, d in zip(entries, shape)]
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(one, slab_specs)


# -------------------------------------------------- HLO collective analysis
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# per-device traffic multiplier per collective kind (ring algorithms)
_TRAFFIC_FACTOR = {
    "all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
}


def _shape_bytes(type_str: str) -> int:
    import re
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Parse an HLO dump; per-device collective traffic bytes by op kind.

    Uses result shapes × ring-traffic factors (all-reduce counts 2x). Returns
    {kind: bytes, ..., "total": bytes}.
    """
    import re
    pat = re.compile(
        r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\][^\s]*))\s+"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")
    out = {k: 0.0 for k in _TRAFFIC_FACTOR}
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        kind = m.group(2)
        out[kind] += _shape_bytes(m.group(1)) * _TRAFFIC_FACTOR[kind]
    out["total"] = sum(out.values())
    return out
