"""Operations and bytes that serving the granite-4.0-h style hybrid needs,
from the configuration's shapes (a config file's keys) alone.

Counts are of the algorithm, not of the program: a matrix product of an
(m, k) by a (k, n) matrix is 2·m·k·n operations. Per token, each Mamba layer
does its projections and the state recurrence (4·H·P·N: the decayed state
plus dt·x outer B, and the read-out against C), each attention layer its
projections and 2·2·heads·head_dim operations per key attended, and every
layer its router, its shared expert and ``k · held / experts`` expert FFNs
(top-k routing spread evenly, only the held experts counted). The conv,
norms, gates and softmaxes are left out.

Bytes: a call reads every weight that is not an expert once; of the held
experts it reads only those its tokens reach, held · (1 − ((E − k) / E)^n)
per layer for n tokens routed evenly over E experts, so no count pays for an
expert that no token used. Per decoded token: the SSM state (float32) and the
conv state read and written, the filled K/V read (positions before its own)
and its new K/V written, its embedding row. A prompt writes each Mamba
layer's state once and its K/V for every position. So a program that pads,
reads empty slots or recomputes can only look slower, never faster than the
chip's peak allows.
"""
from __future__ import annotations


def _m(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"d": d, "di": cfg["mamba_expand"] * d, "H": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
            "G": cfg["mamba_n_groups"], "W": cfg["mamba_d_conv"],
            "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
            "hd": cfg["head_dim"], "f": cfg["intermediate_size"],
            "fs": cfg["shared_intermediate_size"],
            "E": cfg["router_outputs"], "held": cfg["num_local_experts"],
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"],
            "Lm": sum(t == "mamba" for t in kinds),
            "La": sum(t == "attention" for t in kinds)}


def mamba_matmul_params(cfg: dict) -> int:
    """One Mamba layer's projections: in (z, xBC, dt) and out."""
    m = _m(cfg)
    return m["d"] * (2 * m["di"] + 2 * m["G"] * m["N"] + m["H"]) + \
        m["di"] * m["d"]


def attention_params(cfg: dict) -> int:
    m = _m(cfg)
    return 2 * m["d"] * (m["h"] + m["kv"]) * m["hd"]


def expert_params(cfg: dict) -> int:
    m = _m(cfg)
    return 3 * m["d"] * m["f"]


def dense_moe_params(cfg: dict) -> int:
    """One layer's router and shared expert."""
    m = _m(cfg)
    return m["d"] * m["E"] + 3 * m["d"] * m["fs"]


def experts_reached(cfg: dict, tokens: int) -> float:
    """Held experts of one layer that ``tokens`` tokens reach, routed evenly
    over all experts, top k each."""
    m = _m(cfg)
    return m["held"] * (1.0 - ((m["E"] - m["k"]) / m["E"]) ** tokens)


def _layers(cfg: dict) -> int:
    m = _m(cfg)
    return m["Lm"] + m["La"]


def matmul_params_per_token(cfg: dict) -> float:
    """Matrix-product weights one token goes through, head included: the
    mixers, each layer's router and shared expert, and its share of the
    held experts (k · held / E expert FFNs)."""
    m = _m(cfg)
    routed = m["k"] * m["held"] / m["E"]
    return (m["Lm"] * mamba_matmul_params(cfg)
            + m["La"] * attention_params(cfg)
            + _layers(cfg) * (dense_moe_params(cfg)
                              + routed * expert_params(cfg))
            + m["d"] * m["v"])


def weight_bytes(cfg: dict, tokens: int | None = None,
                 itemsize: int = 2) -> float:
    """Bytes of weights a call carrying ``tokens`` tokens reads: every
    weight that is not an expert once (the tied head is the embedding, read
    once; norm scales and the Mamba scalars in float32), and the held
    experts those tokens reach (all held experts when ``tokens`` is None).
    The embedding rows a step looks up are counted with the tokens."""
    m = _m(cfg)
    L = _layers(cfg)
    f32 = (m["Lm"] * (3 * m["H"] + m["di"]) + 2 * L * m["d"] + m["d"])
    conv = m["Lm"] * (m["W"] + 1) * (m["di"] + 2 * m["G"] * m["N"])
    dense = (m["Lm"] * mamba_matmul_params(cfg) + conv
             + m["La"] * attention_params(cfg) + L * dense_moe_params(cfg)
             + m["d"] * m["v"])
    held = m["held"] if tokens is None else experts_reached(cfg, tokens)
    return itemsize * (dense + L * held * expert_params(cfg)) + 4 * f32


def state_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One slot's recurrent state: every Mamba layer's SSM state (float32)
    and conv window."""
    m = _m(cfg)
    conv_ch = m["di"] + 2 * m["G"] * m["N"]
    return m["Lm"] * (4 * m["H"] * m["P"] * m["N"]
                      + itemsize * (m["W"] - 1) * conv_ch)


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position over the attention layers."""
    m = _m(cfg)
    return 2 * m["La"] * m["kv"] * m["hd"] * itemsize


def _ssd_flops(cfg: dict) -> int:
    m = _m(cfg)
    return 4 * m["H"] * m["P"] * m["N"] * m["Lm"]


def attention_flops(cfg: dict, keys: int) -> int:
    """One query token attending ``keys`` cached positions, all attention
    layers."""
    m = _m(cfg)
    return 4 * m["h"] * m["hd"] * keys * m["La"]


def decode_token_flops(cfg: dict, position: int) -> float:
    """One generated token written at cache ``position`` (it attends the
    ``position + 1`` keys up to and including itself)."""
    return (2 * matmul_params_per_token(cfg) + _ssd_flops(cfg)
            + attention_flops(cfg, position + 1))


def decode_token_bytes(cfg: dict, position: int, itemsize: int = 2) -> int:
    """Per token beyond the weights: its slot's recurrent state read and
    written, the filled K/V it reads (positions 0..position-1) and its own
    K/V written, and its embedding row."""
    return (2 * state_bytes(cfg, itemsize)
            + kv_bytes_per_position(cfg, itemsize) * (position + 1)
            + cfg["hidden_size"] * itemsize)


def prefill_flops(cfg: dict, length: int) -> float:
    """An unpadded prompt of ``length`` tokens: every token through every
    layer (state recurrence included), causal attention (token i attends
    i + 1 keys), and the head for the last token only."""
    m = _m(cfg)
    causal_keys = length * (length + 1) // 2
    body = 2 * matmul_params_per_token(cfg) - 2 * m["d"] * m["v"]
    return ((body + _ssd_flops(cfg)) * length
            + 4 * m["h"] * m["hd"] * causal_keys * m["La"]
            + 2 * m["d"] * m["v"])


def prefill_bytes(cfg: dict, length: int, itemsize: int = 2) -> int:
    """A prompt's share beyond the weights: its slot's recurrent state
    written once, its K/V written, and its embedding rows."""
    return (state_bytes(cfg, itemsize)
            + (kv_bytes_per_position(cfg, itemsize)
               + cfg["hidden_size"] * itemsize) * length)


def model_flops_per_token(cfg: dict) -> float:
    """Matrix-product operations of one token through the model, head
    included (2 · the active weights), without the state recurrence and
    attention."""
    return 2 * matmul_params_per_token(cfg)
