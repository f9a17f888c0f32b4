"""The harness off the chip: each cell's body at the program's reduced size
on the CPU, the result line's keys, the refusals, a broken timed path that
must read ``correct: false``, and the traffic generator's seed rule."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
from generator import Traffic  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
SEED = 2 ** 31 + 977           # larger than 32 signed bits, as the driver's


def _cell(name: str) -> dict:
    return harness.load_cell(name)


def _body(name: str, seconds: float = 2.0) -> dict:
    cell = harness.reduced(_cell(name))
    return harness.run_cell(name, SEED, seconds, False,
                            t_start=time.perf_counter(), log=lambda m: None,
                            require_tpu=False, cell=cell)


def _check_line(out: dict, name: str):
    line = json.loads(json.dumps(out))
    assert list(line) == KEYS            # ``checks`` last, no other key
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = [m["name"] for m in _cell(name)["end_to_end"]]
    assert sorted(line["metrics"]) == sorted(want)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


BENCHMARK = harness.load_json(ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_one_chip_body_reduced(name):
    _check_line(_body(name), name)


def test_sweep_reduced():
    """The knee sweep's body at the reduced size: one set-up, rates in
    rising order, a row for each with its tails and backlog."""
    import sweep

    cell = harness.reduced(harness.load_cell("granite-chat"))
    out = sweep.sweep(cell, SEED, [16.0, 8.0], 1.0, 0.5, log=lambda m: None,
                      require_tpu=False)
    assert [r["rate_rps"] for r in out["rows"]] == [8.0, 16.0]
    assert out["slots"] == 32
    for r in out["rows"]:
        assert r["sent"] > 0 and r["ttft_p95_s"] >= r["ttft_p50_s"] > 0
        assert r["xla_compiles"] >= 0
    assert out["knee_rps"] in (None, 8.0, 16.0)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "granite-chat", "--seed", str(SEED), "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_cpu():
    res = _run_py(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "'cpu'" in res.stderr


def test_run_refuses_without_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    has no system to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_py(str(tmp_path))
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_altered_token_reads_incorrect(monkeypatch):
    """The timed path broken underneath: every decode step's logits are
    rolled by one along the vocabulary, so each generated token is the
    neighbour of the one the model chose. ``correct`` must come out
    false on the logit gap."""
    import jax.numpy as jnp

    from repro.models.model import Model

    real = Model.decode

    def altered(self, *a, **k):
        logits, state = real(self, *a, **k)
        return jnp.roll(logits, 1, axis=-1), state

    monkeypatch.setattr(Model, "decode", altered)
    out = _body("granite-chat")
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_unchanged_state_reads_incorrect(monkeypatch):
    """The timed path broken underneath: every decode step hands back the
    cache it was given, so no generated token's keys and values are kept.
    ``correct`` must come out false on the logit gap."""
    from repro.models.model import Model

    real = Model.decode

    def stale(self, params, state, *a, **k):
        logits, _ = real(self, params, state, *a, **k)
        return logits, state

    monkeypatch.setattr(Model, "decode", stale)
    out = _body("granite-chat")
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_reads_above_the_limit():
    """The float8 control put in the program's place, on the tokens the
    program served: its widest gap must fail the limit that the program's
    own gap passes."""
    cell = harness.reduced(harness.load_cell("granite-chat"))
    out = harness.run_cell("granite-chat", SEED, 2.0, False,
                           t_start=time.perf_counter(), log=lambda m: None,
                           require_tpu=False, cell=cell, control=True)
    gap = out["checks"]["logit_gap"]
    ctl = out["control"]
    assert gap["value"] <= gap["limit"] < ctl["checks"]["logit_gap"]["value"]
    assert out["correct"] is True and ctl["correct"] is False


CONTRACT = ("make_params", "logits", "weight_bytes", "decode_token_flops",
            "decode_token_bytes", "prefill_flops", "prefill_bytes",
            "model_flops_per_token", "program_sizes", "reduced_config")

SPY = """
CALLS = []
DENSE = None          # the dense model package, set by the test


def _spy(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(DENSE, name)(*args, **kwargs)
    return call


for _name in {names!r}:
    globals()[_name] = _spy(_name)
"""


def test_second_model_drives_a_cell(tmp_path, monkeypatch):
    """A model directory the harness has never seen, named by the cell's
    configuration, drives a whole reduced cell: its sizes, weights,
    reference and work counts all come from that directory."""
    cell = _cell("granite-chat")
    dense = harness.load_model(cell["config"])
    (tmp_path / "spy").mkdir()
    (tmp_path / "spy" / "__init__.py").write_text(SPY.format(names=CONTRACT))
    monkeypatch.setattr(harness, "MODELS", str(tmp_path))
    cell["config"] = dict(cell["config"], model="spy")
    spy = harness.load_model(cell["config"])
    assert spy is not dense and spy.__name__ != dense.__name__
    spy.DENSE = dense
    ctxs, read = [], harness.load_reader
    monkeypatch.setattr(harness, "load_reader", lambda name: (
        lambda ctx: ctxs.append(ctx) or read(name)(ctx)))
    out = harness.run_cell("granite-chat", SEED, 2.0, False,
                           t_start=time.perf_counter(), log=lambda m: None,
                           require_tpu=False, cell=harness.reduced(cell))
    _check_line(out, "granite-chat")
    assert {"reduced_config", "program_sizes", "make_params",
            "logits"} <= set(spy.CALLS)
    # the model step's readers take their counts from the cell's model
    ctx = ctxs[0]
    assert ctx["flops"] is spy
    busy = {"device_s": 1.0, "count": 1}
    ctx.update(peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
               trace={"classes": {"decode": busy, "prefill": busy}})
    for name in ("decode_roofline", "prefill_roofline", "mfu"):
        assert read(name)(ctx) > 0
    assert {"weight_bytes", "decode_token_flops", "decode_token_bytes",
            "prefill_flops", "prefill_bytes"} <= set(spy.CALLS)


def test_config_without_model_names_its_file(tmp_path, monkeypatch):
    cfg = dict(_cell("granite-chat")["config"])
    del cfg["model"]
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "no-model.json"
    path.write_text(json.dumps(cfg))
    monkeypatch.setattr(harness, "BENCH", str(tmp_path))
    work = {"name": "w", "config": "no-model", "traffic": "chat", "chips": 1}
    with pytest.raises(SystemExit, match="no-model.json"):
        harness.cell_from(work, [], [])


def test_traffic_same_work_for_every_seed():
    spec = harness.load_cell("granite-chat")["traffic"]
    a, b = Traffic(spec, 49155, 1), Traffic(spec, 49155, 2 ** 33 + 1)
    n = spec["block"]
    la = sorted(a.lengths(i) for i in range(n))
    lb = sorted(b.lengths(i) for i in range(n))
    assert sorted(x for x, _ in la) == sorted(x for x, _ in lb)
    assert sorted(y for _, y in la) == sorted(y for _, y in lb)
    assert [a.lengths(i) for i in range(n)] != [b.lengths(i)
                                                for i in range(n)]
    ga = np.diff([0.0] + [a.due(i) for i in range(n)])
    gb = np.diff([0.0] + [b.due(i) for i in range(n)])
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert np.isclose(np.mean(ga), 1.0 / spec["rate_rps"], rtol=0.05)
    assert a.prompt(3) != b.prompt(3)
    assert a.prompt(3) == Traffic(spec, 49155, 1).prompt(3)
    p = spec["prompt"]
    assert all(p["min"] <= len(a.prompt(i)) <= p["max"] for i in range(n))
