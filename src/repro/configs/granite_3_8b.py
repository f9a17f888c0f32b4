"""granite-3-8b [dense] — hf:ibm-granite/granite-3.0 family.

40L d_model=4096 32H (GQA kv=8) d_ff=12800 vocab=49155.
vocab 49155 is not divisible by TP=16; the shard plan pads it (PaddedDims).

``ONE_CHIP`` (``granite-3-8b-1chip``) is what one TPU v5e (16 GB) serves of
it, through the normal control loop:

  * source: the published config above (hf ibm-granite/granite-3.0-8b,
    config.json); every width is kept — hidden 4096, 32 query and 8 KV heads
    of 128, d_ff 12800, vocab 49155, tied embeddings;
  * reduced: ``num_layers`` 40 -> 20;
  * deployment: a 2-stage pipeline over two v5e chips, 20 layers each; this
    is stage 1 (embedding, 20 layers, head). No layer is split across chips,
    so no head, expert or vocabulary slice is cut;
  * assumed: bf16 weights and bf16 KV cache (the checkpoint's dtype); the
    granite muP-style multipliers (embedding, attention, residual, logits
    scaling) are not modelled — the block is the repo's plain pre-norm
    GQA + SwiGLU, and weights are random from a seed.

At bf16 the 20-layer share holds 8.37 GB of weights, leaving the rest of the
chip for the KV slab (see ``chip_smoke.py`` for the sizing it prints).
"""
import dataclasses

from repro.configs.base import ArchConfig, register, register_share

CONFIG = register(ArchConfig(
    name="granite-3-8b",
    family="dense",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=12800,
    vocab_size=49155,
    tie_embeddings=True,
    rope_theta=10_000.0,
    max_seq_len=131_072,
))

ONE_CHIP = register_share(dataclasses.replace(
    CONFIG, name="granite-3-8b-1chip", num_layers=20, dtype="bfloat16"))
