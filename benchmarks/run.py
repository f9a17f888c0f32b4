"""Benchmark entrypoint — one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig1_utilization/*      — paper Fig.1 (resource utilization over time)
  fig2_response/*         — paper Fig.2 (response time vs load)
  fig3_scaling/*          — paper Fig.3 (scaling efficiency vs load)
  claims/*                — the +35% / -28% headline validation
  roofline/*              — per (arch x shape) roofline terms (§Roofline)

Artifacts land under results/ (CSVs + JSON).
"""
from __future__ import annotations

import sys


def main() -> None:
    rows = []
    args = set(sys.argv[1:])
    all_ = not args

    if all_ or "figs" in args:
        from benchmarks.common import get_controller
        from benchmarks.fig_benches import (fig1_utilization,
                                            fig2_response_time,
                                            fig3_scaling_efficiency,
                                            paper_claims)
        controller = get_controller()
        rows += fig1_utilization(controller)
        rows += fig2_response_time(controller)
        rows += fig3_scaling_efficiency(controller)
        rows += paper_claims(controller)
    if all_ or "ablations" in args:
        from benchmarks.ablations import main as ablations_main
        rows += ablations_main()
    if all_ or "roofline" in args:
        from benchmarks.roofline import main as roofline_main
        rows += roofline_main()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
