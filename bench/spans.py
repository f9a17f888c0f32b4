"""The program's own spans in a profiler trace: the ``repro.*`` host spans
that ``repro.obs`` records inside the control plane and the frontend, read
beside the harness's ``bench.*`` spans and the device's busy time.

    python3 bench/spans.py --workload granite-chat --seed 7 --seconds 51

runs the cell as ``bench/run.py --trace 1`` does (set-up, then a window of
``--seconds`` whose first ``harness.TRACE_SECONDS`` are traced) and prints
one JSON line: the traced window's tick, the cell's per-layer readings, the
``spans`` table, ``idle_by_span`` and the control plane's readings:

* ``plane_wait_ms_per_tick``: the plane's blocked fetch seconds
  (``ControlPlane.sync_wait``) over the traced ticks, per tick;
* ``plane_host_ms_per_tick``: the self seconds of every ``repro.plane.*``
  span, per ``repro.plane.step``;
* ``plane_idle_ms_per_tick``: device-idle time inside a
  ``repro.plane.step`` and outside its ``repro.fe.tick``, mean over the
  chips, per ``repro.plane.step``.

Self time is a span's duration less the union of the spans it contains on
its own thread line. The window, busy time, program classes and the
benchmark's idle gaps are ``devtrace.reduce`` over the ``bench.*`` spans
alone, so they read as in the benchmark's traced run. ``idle_by_span``
splits the device's idle time exactly among the innermost host spans over
it, ``bench.*`` and ``repro.*`` alike (a fetch is named with the span it was
made in: ``repro.plane.balance/repro.fetch``). ``--save-trace`` keeps the window's first ``harness.SAVED_TRACE_S``
seconds with the program's spans. A program without spans or a plane wait
counter reads ``None`` for what it lacks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import devtrace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PROGRAM = "repro."
HOST_PREFIXES = (devtrace.SPAN_PREFIX, PROGRAM)


def to_plain(profile) -> list:
    """As ``devtrace.to_plain``, keeping the program's spans too."""
    planes = []
    for plane in profile.planes:
        device = devtrace.DEVICE_PLANE.match(plane.name) is not None
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (devtrace.OPS_LINES
                                            + devtrace.MODULE_LINES):
                continue
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(HOST_PREFIXES)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def _is_device(plane: dict) -> bool:
    return devtrace.DEVICE_PLANE.match(plane["name"]) is not None


def bench_only(planes: list) -> list:
    """The planes with host events cut to the harness's ``bench.*`` spans."""
    out = []
    for p in planes:
        if _is_device(p):
            out.append(p)
            continue
        lines = [{"name": line["name"],
                  "events": [ev for ev in line["events"]
                             if ev[0].startswith(devtrace.SPAN_PREFIX)]}
                 for line in p["lines"]]
        lines = [line for line in lines if line["events"]]
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out


def _host_lines(planes: list) -> list:
    return [line["events"] for p in planes if not _is_device(p)
            for line in p["lines"]]


def _length(intervals: list) -> int:
    return sum(e - s for s, e in devtrace._union(intervals))


def _minus(intervals: list, holes: list) -> list:
    """Sorted disjoint ``intervals`` less the union of ``holes``."""
    out = []
    holes = devtrace._union(holes)
    for s, e in intervals:
        for h0, h1 in holes:
            if h1 <= s or h0 >= e:
                continue
            if h0 > s:
                out.append([s, h0])
            s = max(s, h1)
        if s < e:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _innermost(spans: list) -> list:
    """The host timeline as disjoint ``[start, end, label]`` pieces, each
    labelled with the innermost span over it (the spans of one thread
    nest); a fetch is labelled with the span it was made in."""
    out, stack, t = [], [], 0
    fetch = PROGRAM + "fetch"

    def close():
        nonlocal t
        end, label, _ = stack.pop()
        out.append([t, end, label])
        t = end

    for name, s, d in sorted(spans, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            out.append([t, s, stack[-1][1]])
        label = f"{stack[-1][2]}/{name}" if name == fetch and stack else name
        stack.append((s + d, label, name))
        t = s
    while stack:
        close()
    return [piece for piece in out if piece[1] > piece[0]]


def _split(idle: list, pieces: list, into: dict, scale: float) -> None:
    """Add each idle interval's overlap with each labelled piece to
    ``into[label]`` (and what no piece covers to ``outside_spans``), times
    ``scale``."""
    j = 0
    for g0, g1 in idle:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            lap = min(g1, pieces[k][1]) - max(g0, pieces[k][0])
            if lap > 0:
                into[pieces[k][2]] = into.get(pieces[k][2], 0.0) + lap * scale
                covered += lap
            k += 1
        if g1 - g0 > covered:
            into["outside_spans"] = into.get("outside_spans", 0.0) + \
                (g1 - g0 - covered) * scale


def span_table(planes: list) -> dict:
    """For each ``repro.*`` name: its count, total seconds and self seconds
    (duration less the union of the spans inside it on its thread line)."""
    table: dict = {}
    for events in _host_lines(planes):
        evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
        for i, (name, s, d) in enumerate(evs):
            if not name.startswith(PROGRAM):
                continue
            inner = []
            for j in range(i + 1, len(evs)):
                s2, d2 = evs[j][1], evs[j][2]
                if s2 >= s + d:
                    break
                if s2 + d2 <= s + d:
                    inner.append([s2, s2 + d2])
            row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                          "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += d / 1e9
            row["self_s"] += (d - _length(inner)) / 1e9
    return table


def _idle(planes: list, chips: int, lo: int, hi: int) -> list:
    """Per traced chip, the window's idle intervals (the window less the
    union of the intervals in which an operation runs)."""
    devices = sorted((p for p in planes if _is_device(p)),
                     key=lambda p: int(devtrace.DEVICE_PLANE.match(
                         p["name"])[1]))[:chips]
    out = []
    for p in devices:
        lines = {line["name"]: line["events"] for line in p["lines"]}
        ops = [ev for n in devtrace.OPS_LINES for ev in lines.get(n, [])]
        mods = [ev for n in devtrace.MODULE_LINES for ev in lines.get(n, [])]
        busy = devtrace._clip(devtrace._union(
            [[s, s + d] for _, s, d in (ops or mods)]), lo, hi)
        out.append(_minus([[lo, hi]], busy))
    return out


def reduce(planes: list, chips: int) -> dict:
    """``devtrace.reduce`` over the ``bench.*`` spans, and the program's
    spans: ``spans`` (``span_table``), ``idle_by_span`` (idle seconds under
    each innermost host span, ``_innermost``, mean over the chips),
    ``plane_steps`` and ``plane_idle_s`` (idle seconds inside a
    ``repro.plane.step`` and outside its ``repro.fe.tick``, mean over the
    chips)."""
    bench = bench_only(planes)
    red = devtrace.reduce(bench, chips)
    marks = [ev for events in _host_lines(bench) for ev in events]
    lo = min(s for _, s, _ in marks)
    hi = max(s + d for _, s, d in marks)
    idle = _idle(planes, chips, lo, hi)
    n = max(len(idle), 1)
    spans = [ev for events in _host_lines(planes) for ev in events]
    pieces = _innermost(spans)
    gaps: dict = {}
    for dev in idle:
        _split(dev, pieces, gaps, 1e-9 / n)
    steps = devtrace._clip(
        sorted([s, s + d] for nm, s, d in spans
               if nm == PROGRAM + "plane.step"), lo, hi)
    ticks = [[s, s + d] for nm, s, d in spans if nm == PROGRAM + "fe.tick"]
    own = _minus(steps, ticks)
    red["spans"] = span_table(planes)
    red["idle_by_span"] = sorted(gaps.items(), key=lambda kv: -kv[1])
    red["plane_steps"] = len(steps)
    red["plane_idle_s"] = sum(_overlap(dev, own) for dev in idle) / 1e9 / n
    return red


def plane_host_ms_per_tick(red: dict):
    steps = red["spans"].get(PROGRAM + "plane.step")
    if not steps or not steps["count"]:
        return None
    own = sum(row["self_s"] for name, row in red["spans"].items()
              if name.startswith(PROGRAM + "plane."))
    return 1e3 * own / steps["count"]


def plane_idle_ms_per_tick(red: dict):
    if not red.get("plane_steps") or not red["chips_traced"]:
        return None
    return 1e3 * red["plane_idle_s"] / red["plane_steps"]


# -------------------------------------------------------------- the run
def run(workload: str, seed: int, seconds: float, *, log,
        save_trace: str | None = None, require_tpu: bool = True,
        cell: dict | None = None) -> dict:
    """One traced run of ``workload`` with the program's spans kept."""
    import shutil
    import tempfile

    import jax

    import harness

    cell = cell or harness.load_cell(workload)
    s = harness.set_up(cell, seed, True, log=log, require_tpu=require_tpu)
    counters = harness.Counters()
    wait0 = getattr(s.plane, "sync_wait", None)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    w0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    tw0 = time.perf_counter()
    s.drv.run_until(tw0 + min(seconds, harness.TRACE_SECONDS), counters)
    tw1 = time.perf_counter()
    jax.profiler.stop_trace()
    wait1 = getattr(s.plane, "sync_wait", None)
    s.drv.run_until(w0 + seconds)
    planes = to_plain(_profile(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if save_trace:
        devtrace.save(planes, save_trace, harness.SAVED_TRACE_S)
    red = reduce(planes, int(cell["workload"]["chips"]))
    del planes
    ctx = {"cfg": s.cfg, "peaks": s.peaks,
           "flops": harness.load_model(s.cfg),
           "records": s.drv.records, "window": (tw0, tw1), "setup_s": None,
           "counters": counters, "trace": red}
    out = {"workload": workload, "seed": seed,
           "device": s.used[0].device_kind, "ticks": counters.ticks,
           "tick_ms": 1e3 * (tw1 - tw0) / max(counters.ticks, 1),
           "window_s": red["window_s"], "busy_s": red["busy_s"]}
    for m in cell["per_layer"]:
        out[m["name"]] = harness.load_reader(m["name"])(ctx)
    out["plane_wait_ms_per_tick"] = (
        1e3 * (wait1 - wait0) / counters.ticks
        if wait0 is not None and counters.ticks else None)
    out["plane_host_ms_per_tick"] = plane_host_ms_per_tick(red)
    out["plane_idle_ms_per_tick"] = plane_idle_ms_per_tick(red)
    out["plane_steps"] = red["plane_steps"]
    out["spans"] = red["spans"]
    out["idle_gaps"] = red["idle_gaps"]
    out["idle_by_span"] = red["idle_by_span"]
    return out


def _profile(trace_dir: str):
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(sorted(paths)[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save-trace", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness

    harness.enable_cache()
    out = run(args.workload, args.seed, args.seconds,
              save_trace=args.save_trace,
              log=lambda m: print(f"[spans] {m}", file=sys.stderr,
                                  flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
