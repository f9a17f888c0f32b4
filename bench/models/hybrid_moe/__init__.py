"""The granite-4.0-h style hybrid that ``repro.models`` serves as its
``hybrid_moe`` family (a Mamba-2 or attention mixer per layer, each followed
by a held-expert MoE FFN beside a shared expert): weights from the seed
(``weights.py``), the plain float32 reference (``reference.py``) and the
work counts (``flops.py``), by the contract in ``bench/modelkit.py``."""
from __future__ import annotations

from .flops import (decode_token_bytes, decode_token_flops,  # noqa: F401
                    model_flops_per_token, prefill_bytes, prefill_flops,
                    weight_bytes)
from .reference import logits  # noqa: F401
from .weights import make_params  # noqa: F401


def program_sizes(cfg: dict) -> dict:
    """The program's ``ArchConfig`` attributes that the file's sizes fix."""
    nope = cfg["position_embedding_type"] == "nope"
    return {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "moe_shared_d_ff": cfg["shared_intermediate_size"],
            "num_layers": cfg["num_hidden_layers"],
            "layer_types": tuple(cfg["layer_types"]),
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "resolved_head_dim": cfg["head_dim"],
            "vocab_size": cfg["vocab_size"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "rope_theta": 0.0 if nope else cfg["rope_theta"],
            "norm_eps": cfg["rms_norm_eps"],
            "num_experts": cfg["router_outputs"],
            "experts_held": cfg["num_local_experts"],
            "num_experts_per_tok": cfg["num_experts_per_tok"],
            "ssm_heads": cfg["mamba_n_heads"],
            "ssm_head_dim": cfg["mamba_d_head"],
            "ssm_state": cfg["mamba_d_state"],
            "ssm_groups": cfg["mamba_n_groups"],
            "ssm_expand": cfg["mamba_expand"],
            "ssm_conv_width": cfg["mamba_d_conv"],
            "ssm_chunk": cfg["mamba_chunk_size"],
            "embedding_multiplier": cfg["embedding_multiplier"],
            "attention_multiplier": cfg["attention_multiplier"],
            "residual_multiplier": cfg["residual_multiplier"],
            "logits_scaling": cfg["logits_scaling"],
            "dtype": cfg["torch_dtype"]}


def reduced_config(cfg: dict, a) -> dict:
    """The file with its sizes cut to ``a``, the program's reduced
    ``ArchConfig``."""
    return dict(cfg, hidden_size=a.d_model, intermediate_size=a.d_ff,
                shared_intermediate_size=a.moe_shared_d_ff,
                num_hidden_layers=a.num_layers,
                num_attention_heads=a.num_heads,
                num_key_value_heads=a.num_kv_heads,
                head_dim=a.resolved_head_dim, vocab_size=a.vocab_size,
                router_outputs=a.num_experts,
                num_local_experts=a.experts_held,
                num_experts_per_tok=a.num_experts_per_tok,
                mamba_n_heads=a.ssm_heads, mamba_d_head=a.ssm_head_dim,
                mamba_d_state=a.ssm_state, mamba_chunk_size=a.ssm_chunk,
                torch_dtype=a.dtype)
