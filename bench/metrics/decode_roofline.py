"""Decode programs' share of their roofline, in percent: the least time the
chip needs for the decode work in the traced window over the programs'
device time. The work is the weights once per execution on each chip, and
per generated token its matrix products, attention over the filled cache,
the filled cache read and its new K/V written (the model's counts,
``ctx["flops"]``). Sums are taken before the larger bound is chosen, which
can only lower the share."""


def read(ctx):
    tr = ctx["trace"]
    row = tr and tr["classes"].get("decode")
    pos = ctx["counters"].decode_positions
    if not row or not row["device_s"] or not pos:
        return None
    cfg, f, pk = ctx["cfg"], ctx["flops"], ctx["peaks"]
    ops = sum(f.decode_token_flops(cfg, p) for p in pos)
    nbytes = (row["count"] * f.weight_bytes(cfg)
              + sum(f.decode_token_bytes(cfg, p) for p in pos))
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / row["device_s"]
