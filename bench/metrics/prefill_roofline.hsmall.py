"""Prefill programs' share of their roofline, in percent, for a model whose
weight bytes depend on the tokens a call carries (``weight_bytes(cfg,
tokens)`` of the cell's model): the least time for the unpadded prompts
admitted in the traced window over the prefill programs' device time. The
prompts whose first tokens reached their clients at one host-clock stamp
count as one call of all their tokens, and the traced calls beyond those
read the weights that are not experts; a tick's calls then count the held
experts of their tokens together once, which can only lower the share, as
taking the sums before the larger bound does."""
from collections import Counter


def read(ctx):
    tr = ctx["trace"]
    row = tr and tr["classes"].get("prefill")
    prompts = ctx["counters"].prompts
    if not row or not row["device_s"] or not prompts:
        return None
    w0, w1 = ctx["window"]
    calls = Counter()
    for r in ctx["records"]:
        if r.times and w0 <= r.times[0] <= w1:
            calls[r.times[0]] += len(r.req.prompt)
    cfg, f, pk = ctx["cfg"], ctx["flops"], ctx["peaks"]
    ops = sum(f.prefill_flops(cfg, n) for n in prompts)
    nbytes = (sum(f.weight_bytes(cfg, n) for n in calls.values())
              + max(0, row["count"] - len(calls)) * f.weight_bytes(cfg, 0)
              + sum(f.prefill_bytes(cfg, n) for n in prompts))
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / row["device_s"]
