"""granite-4.0-h-small [hybrid_moe] — hf:ibm-granite/granite-4.0-h-small
(config.json, ``model_type`` granitemoehybrid, 32B-A9B).

40 layers in a 10-layer period of 9 Mamba-2 mixers and 1 attention mixer
(attention at layers 5, 15, 25, 35). Mamba-2: 128 heads of 64, d_state 128,
one group, expand 2 (d_inner 8192), conv 4 with bias, chunk 256. Attention:
GQA 32 query / 8 KV heads of 128, NoPE (no rotary embedding), softmax scale
``attention_multiplier`` 1/128. Every layer is followed by an MoE FFN of 72
experts of width 768, top-10, and a shared SwiGLU expert of width 1536.
muP scalars: embedding x12, residual branch x0.22, logits /16. Hidden 4096,
RMSNorm eps 1e-5, vocabulary 100352 with tied embeddings.

``ONE_CHIP`` (``granite-4.0-h-small-1chip``) is what one TPU v5e (16 GB)
serves of it, through the normal control loop:

  * source: the published config above; every width is kept — every Mamba
    and attention head, the expert and shared-expert widths, the router's 72
    outputs and top-10, the whole vocabulary;
  * reduced: ``num_layers`` 40 -> 20 (layers 0-19, attention at 5 and 15:
    two whole periods) and the experts held 72 -> 9 (experts 0-8);
  * deployment: a 2-stage pipeline, each stage's layers split over 8 chips
    by expert parallelism (9 experts a chip, attention and Mamba run data-
    parallel over those 8 chips); this chip is stage 1 with experts 0-8, the
    embedding and the tied head. The router still routes over all 72; this
    chip computes its own experts' part for the tokens routed to them, and
    what the other 63 would add is left out;
  * assumed: random weights from a seed in bf16; the SSM state in float32 (the
    checkpoint's dtype is bf16); the KV cache and the conv state in bf16.

At bf16 the share holds 8.84 GB of weights (4.42 B parameters); a slot of 512
positions holds 75.5 MB of SSM state, 0.9 MB of conv state and 4.2 MB of K/V.
"""
import dataclasses

from repro.configs.base import ArchConfig, register, register_share

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = register(ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    tie_embeddings=True,
    norm_eps=1e-5,
    rope_theta=0.0,                  # NoPE
    max_seq_len=131_072,
    num_experts=72,
    num_experts_per_tok=10,
    moe_shared_d_ff=1536,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv_width=4,
    ssm_groups=1,
    ssm_chunk=256,
    layer_types=PERIOD * 4,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
))

ONE_CHIP = register_share(dataclasses.replace(
    CONFIG, name="granite-4.0-h-small-1chip", num_layers=20, moe_held=9,
    dtype="bfloat16"))
