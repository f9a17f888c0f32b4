"""The program's spans in a trace (``spans.py``) and the admission-wait
reader: self time, the plane's share of device idle by exact interval
intersection, the benchmark's own reduction left as it was, and a quarter
second recorded on the chip with the program's spans."""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import spans  # noqa: E402

MS = 1_000_000     # ns
BENCH_KEYS = ("chips_traced", "window_s", "busy_s", "classes",
              "top_programs", "idle_gaps")


def _ms(a, b):
    return round(a * MS), round((b - a) * MS)


def _hand_trace(program: bool = True):
    """The harness's spans and one device's programs; with ``program`` the
    plane's and the frontend's spans nest inside them."""
    host = [["bench.plane_step", *_ms(0, 10)],
            ["bench.fe_tick", *_ms(4, 8)],
            ["bench.stamp", *_ms(10, 12)],
            ["bench.submit", *_ms(12, 20)]]
    if program:
        host += [["repro.plane.step", *_ms(0.5, 9.5)],
                 ["repro.plane.forecast", *_ms(0.5, 3)],
                 ["repro.fetch", *_ms(1, 2.5)],
                 ["repro.plane.balance", *_ms(3, 3.8)],
                 ["repro.fe.tick", *_ms(4.2, 7.8)],
                 ["repro.fe.reconcile", *_ms(4.2, 5)],
                 ["repro.fe.decode", *_ms(6, 7)],
                 ["repro.plane.scale", *_ms(8, 9.5)]]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [["jit__afleet_fn(12)", *_ms(2, 7)],
                    ["jit__afleet_prefill_fn(7)", *_ms(13, 16)],
                    ["jit_actor_action(3)", *_ms(17, 18)]]},
        {"name": "XLA Ops",
         "events": [["fusion.1", *_ms(2, 5)], ["fusion.2", *_ms(4, 7)],
                    ["fusion.3", *_ms(13, 16)], ["dot.4", *_ms(17, 18)]]}]}
    return [{"name": "/host:CPU",
             "lines": [{"name": "python", "events": host}]}, dev]


def test_self_time_is_duration_less_children():
    table = spans.reduce(_hand_trace(), chips=1)["spans"]
    want = {  # (count, total ms, self ms)
        # 9 ms less forecast, balance, bench.fe_tick (with fe.tick), scale
        "repro.plane.step": (1, 9.0, 9.0 - (3.3 + 4.0 + 1.5)),
        "repro.plane.forecast": (1, 2.5, 1.0),
        "repro.fetch": (1, 1.5, 1.5),
        "repro.plane.balance": (1, 0.8, 0.8),
        "repro.fe.tick": (1, 3.6, 3.6 - 0.8 - 1.0),
        "repro.fe.reconcile": (1, 0.8, 0.8),
        "repro.fe.decode": (1, 1.0, 1.0),
        "repro.plane.scale": (1, 1.5, 1.5)}
    assert set(table) == set(want)
    for name, (count, total, own) in want.items():
        row = table[name]
        assert row["count"] == count, name
        assert row["total_s"] == pytest.approx(total / 1e3), name
        assert row["self_s"] == pytest.approx(own / 1e3), name


def test_plane_host_and_idle_per_tick():
    red = spans.reduce(_hand_trace(), chips=1)
    # self times of the plane's spans: step 0.2, forecast 1.0, balance 0.8,
    # scale 1.5 ms; the fetch and the frontend's tick are not the plane's
    assert spans.plane_host_ms_per_tick(red) == pytest.approx(3.5)
    # idle 0-2 and 7-13 ms; the plane's own time is 0.5-9.5 less the
    # frontend's 4.2-7.8: 0.5-2 (1.5 ms) and, from the gap that straddles
    # the end of fe.tick, 7.8-9.5 (1.7 ms)
    assert red["plane_steps"] == 1
    assert red["plane_idle_s"] == pytest.approx(0.0032)
    assert spans.plane_idle_ms_per_tick(red) == pytest.approx(3.2)


def test_idle_split_by_innermost_span():
    red = spans.reduce(_hand_trace(), chips=1)
    got = dict(red["idle_by_span"])
    # idle 0-2, 7-13, 16-17 and 18-20 ms, cut where the innermost span
    # changes; the fetch is named with the span it was made in
    want = {"bench.plane_step": 0.5 + 0.5, "repro.plane.forecast": 0.5,
            "repro.plane.forecast/repro.fetch": 1.0, "repro.fe.tick": 0.8,
            "bench.fe_tick": 0.2, "repro.plane.scale": 1.5,
            "bench.stamp": 2.0, "bench.submit": 1.0 + 1.0 + 2.0}
    assert set(got) == set(want)
    for name, ms in want.items():
        assert got[name] == pytest.approx(ms / 1e3), name
    assert sum(got.values()) == pytest.approx(red["window_s"]
                                              - red["busy_s"])


def test_bench_reduction_unchanged_by_program_spans():
    """The window, busy time, classes, top programs and idle gaps read as
    the benchmark reads them without the program's spans."""
    plain = devtrace.reduce(_hand_trace(program=False), chips=1)
    red = spans.reduce(_hand_trace(), chips=1)
    for key in BENCH_KEYS:
        assert red[key] == plain[key], key
    assert spans.bench_only(_hand_trace()) == _hand_trace(program=False)


def test_plane_readings_silent_without_program_spans():
    red = spans.reduce(_hand_trace(program=False), chips=1)
    assert red["spans"] == {} and red["plane_steps"] == 0
    assert spans.plane_host_ms_per_tick(red) is None
    assert spans.plane_idle_ms_per_tick(red) is None


def _rec(submitted, admitted, times):
    return SimpleNamespace(req=SimpleNamespace(submitted_s=submitted,
                                               admitted_s=admitted),
                           times=times)


def test_queue_wait_reader():
    import harness

    read = harness.load_reader("queue_wait_p95_s")
    assert read({"records": [], "window": (0.0, 1.0)}) is None
    # a program that does not stamp its requests reads nothing
    unstamped = [SimpleNamespace(req=SimpleNamespace(), times=[0.5])]
    assert read({"records": unstamped, "window": (0.0, 1.0)}) is None
    # accepted before or after the window: left out; admitted after the
    # close or not at all: waits to the close
    recs = [_rec(-1.0, 0.5, [0.6]), _rec(1.0, 1.5, [1.6, 2.0]),
            _rec(2.0, 2.25, [2.3, 3.0]), _rec(3.5, 4.5, [4.6]),
            _rec(3.0, None, []), _rec(4.0, 4.1, [4.2])] + \
        [_rec(2.5, 2.5, [2.6]) for _ in range(16)]
    got = read({"records": recs, "window": (0.0, 4.0)})
    waits = [0.5, 0.25, 0.5, 1.0] + [0.0] * 16
    assert got == pytest.approx(float(np.percentile(waits, 95)))


def test_traced_run_off_the_chip():
    """``spans.run`` at the program's reduced size on the CPU: every tick
    is one ``repro.plane.step`` holding one forecast and one balance, each
    with its fetch, and the plane's wait counter reads; no device was
    traced, so no idle reads."""
    import harness

    cell = harness.reduced(harness.load_cell("granite-chat"))
    out = spans.run("granite-chat", 2 ** 31 + 977, 1.5, log=lambda m: None,
                    require_tpu=False, cell=cell)
    assert out["device"] == "cpu" and out["ticks"] > 0
    table = out["spans"]
    for name in ("plane.step", "plane.forecast", "plane.observe",
                 "plane.balance", "plane.learn", "plane.scale", "fe.tick",
                 "fe.reconcile", "fe.route", "fe.admit", "fe.decode"):
        assert table["repro." + name]["count"] == out["ticks"], name
    assert table["repro.fetch"]["count"] >= 3 * out["ticks"]
    assert out["plane_steps"] == out["ticks"]
    assert out["plane_wait_ms_per_tick"] > 0
    assert out["plane_host_ms_per_tick"] > 0
    assert out["plane_idle_ms_per_tick"] is None
    assert out["queue_wait_p95_s"] is not None


CHIP_OLD = os.path.join(BENCH, "testdata", "granite-chat-v5e.json.gz")
CHIP_SPANS = os.path.join(BENCH, "testdata", "granite-chat-v5e-spans.json.gz")


def test_old_chip_slice_reads_as_before():
    planes = devtrace.load_saved(CHIP_OLD)
    plain = devtrace.reduce(planes, chips=1)
    red = spans.reduce(planes, chips=1)
    for key in BENCH_KEYS:
        assert red[key] == plain[key], key
    assert spans.plane_idle_ms_per_tick(red) is None


def _decode_gaps(planes):
    """The device's idle intervals longer than 1 ms between the end of one
    decode execution and the start of the next."""
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    mods = next(line["events"] for line in dev["lines"]
                if line["name"] == "XLA Modules")
    dec = sorted([s, s + d] for n, s, d in mods if "afleet_fn" in n)
    marks = [ev for p in spans.bench_only(planes) if not spans._is_device(p)
             for line in p["lines"] for ev in line["events"]]
    lo = min(s for _, s, _ in marks)
    hi = max(s + d for _, s, d in marks)
    idle = spans._idle(planes, 1, lo, hi)[0]
    out = []
    for (_, e0), (s1, _) in zip(dec, dec[1:]):
        out += [g for g in spans._minus(idle, [[lo, e0], [s1, hi]])
                if g[1] - g[0] > MS]
    return out


def test_chip_slice_with_program_spans():
    """A quarter second of a traced granite-chat window at 8 req/s with the
    program's spans, recorded on one TPU v5e with ``bench/spans.py
    --save-trace``: the plane's spans lie inside the harness's, the idle
    between decodes is the plane's and the frontend's time, and the
    benchmark's own reduction does not see the program's spans."""
    planes = devtrace.load_saved(CHIP_SPANS)
    red = spans.reduce(planes, chips=1)
    events = [ev for p in planes if not spans._is_device(p)
              for line in p["lines"] for ev in line["events"]]
    outer = [(s, s + d) for n, s, d in events if n == "bench.plane_step"]
    steps = [(s, s + d) for n, s, d in events if n == "repro.plane.step"]
    assert steps
    for s, e in steps:
        assert any(s0 <= s and e <= e1 for s0, e1 in outer)
    program = devtrace._union(
        [[s, s + d] for n, s, d in events
         if n.startswith(("repro.plane.", "repro.fe."))])
    gaps = _decode_gaps(planes)
    assert gaps
    for g in gaps:
        covered = spans._overlap([g], program)
        assert covered > (g[1] - g[0]) / 2, g
    tick_ms = 1e3 * red["window_s"] / len(outer)
    assert 0 < spans.plane_idle_ms_per_tick(red) < tick_ms
    whole = devtrace.reduce(planes, chips=1)
    for key in ("window_s", "busy_s", "classes", "top_programs"):
        assert whole[key] == red[key], key
