"""The trace reduction on a hand-made trace in the layout ``devtrace.to_plain``
reads from the profiler: busy union, program classes, idle gaps."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import devtrace  # noqa: E402

MS = 1_000_000     # ns


def _plane(name, lines):
    return {"name": name, "lines": [{"name": n, "events": evs}
                                    for n, evs in lines.items()]}


def _hand_trace():
    host = _plane("/host:CPU", {"python": [
        ["bench.plane_step", 0, 10 * MS],
        ["bench.fe_tick", 1 * MS, 8 * MS],
        ["bench.stamp", 10 * MS, 2 * MS],
        ["bench.submit", 12 * MS, 8 * MS]]})
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [["jit__afleet_fn(12)", 2 * MS, 5 * MS],
                        ["jit__afleet_prefill_fn(7)", 13 * MS, 3 * MS],
                        ["jit_actor_action(3)", 17 * MS, 1 * MS]],
        # two ops overlap: the union counts their span once
        "XLA Ops": [["fusion.1", 2 * MS, 3 * MS], ["fusion.2", 4 * MS, 3 * MS],
                    ["fusion.3", 13 * MS, 3 * MS], ["dot.4", 17 * MS, 1 * MS]]})
    return [host, dev]


def test_busy_union_classes_and_gaps():
    red = devtrace.reduce(_hand_trace(), chips=1)
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx(0.009)     # 2-7, 13-16, 17-18 ms
    assert red["classes"]["decode"] == {"count": 1, "device_s": 0.005}
    assert red["classes"]["prefill"]["device_s"] == pytest.approx(0.003)
    assert red["classes"]["plane"]["count"] == 1
    gaps = dict(red["idle_gaps"])
    # each gap goes to the shortest span that covers its midpoint: 0-2 ms
    # (midpoint 1) to fe_tick inside plane_step, 7-13 ms (midpoint 10) to
    # stamp, 16-17 and 18-20 ms to submit
    assert gaps["bench.fe_tick"] == pytest.approx(0.002)
    assert gaps["bench.stamp"] == pytest.approx(0.006)
    assert gaps["bench.submit"] == pytest.approx(0.001 + 0.002)
    assert sum(gaps.values()) == pytest.approx(0.020 - 0.009)
    assert [n for n, _ in red["top_programs"]][0] == "jit__afleet_fn"


def test_needs_host_spans():
    with pytest.raises(ValueError):
        devtrace.reduce([_hand_trace()[1]], chips=1)


def test_saved_slice_round_trip(tmp_path):
    """``--save-trace`` keeps the events that overlap the window's first
    seconds, in the layout the reduction reads."""
    path = str(tmp_path / "slice.json.gz")
    devtrace.save(_hand_trace(), path, 0.0115)
    planes = devtrace.load_saved(path)
    names = {ev[0] for p in planes for line in p["lines"]
             for ev in line["events"]}
    assert "bench.submit" not in names and "jit_actor_action(3)" not in names
    assert {"bench.plane_step", "bench.stamp", "jit__afleet_fn(12)"} <= names
    red = devtrace.reduce(planes, chips=1)
    assert red["window_s"] == pytest.approx(0.012)
    assert red["busy_s"] == pytest.approx(0.005)


def _readers():
    import harness

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    return harness, [m["name"] for m in bench["per_layer"]]


def test_readers_silent_without_readings():
    """A reader that finds nothing to read returns nothing, never 0."""
    harness, names = _readers()
    ctx = {"cfg": None, "peaks": None, "flops": None, "records": [],
           "window": (0.0, 1.0), "setup_s": 1.0,
           "counters": harness.Counters(), "trace": None}
    for name in names:
        assert harness.load_reader(name)(ctx) is None, name


def test_step_mfu_beside_prefill_roofline():
    """``mfu.ttft`` is the whole step's share of the peak, as ``mfu``."""
    harness, _ = _readers()
    cfg = harness.load_json(BENCH, "configs", "granite-3-8b-1chip.json")
    c = harness.Counters()
    c.prompts, c.decode_positions = [20, 37], list(range(40, 100))
    red = devtrace.reduce(_hand_trace(), chips=1)
    ctx = {"cfg": cfg, "peaks": {"bf16_flops": 197e12,
                                 "hbm_bytes_per_s": 819e9},
           "flops": harness.load_model(cfg), "counters": c, "trace": red}
    got = harness.load_reader("mfu.ttft")(ctx)
    assert got == harness.load_reader("mfu")(ctx) and 0 < got < 100


CHIP_SLICE = os.path.join(BENCH, "testdata", "granite-chat-v5e.json.gz")


def test_chip_recorded_slice():
    """A quarter second of a traced granite-chat window (its mix at 11.2
    req/s, so that a prefill falls in it), recorded on one TPU v5e with
    ``--save-trace``: the program table finds the serve and
    plane programs by the names the chip gives them, and busy time and idle
    gaps fill the window."""
    planes = devtrace.load_saved(CHIP_SLICE)
    assert [p["name"] for p in planes if p["name"].startswith("/device")] \
        == ["/device:TPU:0"]
    red = devtrace.reduce(planes, chips=1)
    assert red["chips_traced"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert {"decode", "prefill", "plane"} <= set(red["classes"])
    assert red["classes"]["decode"]["count"] > 0
    table = devtrace.program_table()
    top = red["top_programs"][0][0]
    assert next(c for c, pat in table if pat.search(top)) == "decode"
    for name, _ in red["top_programs"]:
        if "afleet" in name:
            assert any(pat.search(name) for _, pat in table), name
    gaps = sum(s for _, s in red["idle_gaps"])
    assert gaps == pytest.approx(red["window_s"] - red["busy_s"])
