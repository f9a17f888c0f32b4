"""Decode programs' share of their roofline, in percent, for a model whose
weight bytes depend on the tokens a call carries (held experts are read only
as far as the call's tokens reach them: ``weight_bytes(cfg, tokens)`` of the
cell's model). The tokens of one decode call are those its clients received
at one host-clock stamp (one stamp a tick, one decode call a tick); a call
in the trace with no stamp reads the weights that are not experts. Per
generated token: its matrix products, state recurrence and attention, its
slot's state read and written, the filled K/V read and its new K/V written.
Sums are taken before the larger bound is chosen, which can only lower the
share."""
from collections import Counter


def read(ctx):
    tr = ctx["trace"]
    row = tr and tr["classes"].get("decode")
    pos = ctx["counters"].decode_positions
    if not row or not row["device_s"] or not pos:
        return None
    w0, w1 = ctx["window"]
    calls = Counter(t for r in ctx["records"] for t in r.times[1:]
                    if w0 <= t <= w1)
    cfg, f, pk = ctx["cfg"], ctx["flops"], ctx["peaks"]
    ops = sum(f.decode_token_flops(cfg, p) for p in pos)
    nbytes = (sum(f.weight_bytes(cfg, n) for n in calls.values())
              + max(0, row["count"] - len(calls)) * f.weight_bytes(cfg, 0)
              + sum(f.decode_token_bytes(cfg, p) for p in pos))
    least = max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / row["device_s"]
