"""The dense GQA + SwiGLU decoder that ``repro.models`` serves as its
``dense`` family: weights from the seed (``weights.py``), the plain float32
reference (``reference.py``) and the work counts (``flops.py``), by the
contract in ``bench/modelkit.py``."""
from __future__ import annotations

from .flops import (decode_token_bytes, decode_token_flops,  # noqa: F401
                    model_flops_per_token, prefill_bytes, prefill_flops,
                    weight_bytes)
from .reference import logits  # noqa: F401
from .weights import make_params  # noqa: F401


def program_sizes(cfg: dict) -> dict:
    """The program's ``ArchConfig`` attributes that the file's sizes fix."""
    return {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_layers": cfg["num_hidden_layers"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "resolved_head_dim": cfg["head_dim"],
            "vocab_size": cfg["vocab_size"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
            "dtype": cfg["torch_dtype"]}


def reduced_config(cfg: dict, a) -> dict:
    """The file with its sizes cut to ``a``, the program's reduced
    ``ArchConfig``."""
    return dict(cfg, hidden_size=a.d_model, intermediate_size=a.d_ff,
                num_hidden_layers=a.num_layers,
                num_attention_heads=a.num_heads,
                num_key_value_heads=a.num_kv_heads,
                head_dim=a.resolved_head_dim, vocab_size=a.vocab_size,
                torch_dtype=a.dtype)
