"""The readers of what the program recorded in the profiler session
(``repro.obs.profiled()``): exact on hand-built records, silent on empty
ones, and reported by a traced run of the tiny cell on the CPU."""
import json

import pytest

from bench import harness
from bench import trace as T
from conftest import BENCH

from repro.obs import profiled

NAMES = ("queue_wait_ms", "prefill_ms", "prefill_cycle_share",
         "host_busy_share")


def reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py").read


@pytest.fixture
def record():
    rec = profiled()
    rec.clear()
    yield rec
    rec.clear()


def ctx_of(window_ns=1e9):
    return {"trace": T.Trace({}, [], (0.0, window_ns))}


def fill(rec):
    """Three cycles in a 1 s window: steps of 100, 200 and 300 ms, each
    waiting 50 ms at retire; two admissions and two first tokens."""
    ms = 1_000_000
    for i, (dur, pre, rows) in enumerate(((100, 0, 2), (200, 64, 2),
                                          (300, 32, 0))):
        t = i * 400 * ms
        rec.push(("X", "step.submit", "engine", t, ms,
                  {"prefill_tokens": pre, "decode_rows": rows}))
        rec.push(("X", "retire.wait", "engine", t + 2 * ms, 50 * ms, None))
        rec.push(("X", "step", "engine", t, dur * ms, None))
    rec.push(("i", "request.admit", "req0", 0, 0, {"rid": 0,
                                                   "queue_s": 0.1}))
    rec.push(("i", "request.admit", "req1", 5, 0, {"rid": 1,
                                                   "queue_s": 0.3}))
    rec.push(("i", "request.first_token", "req0", 7, 0, {"rid": 0,
                                                         "prefill_s": 0.5}))
    rec.push(("i", "request.first_token", "req1", 9, 0, {"rid": 1,
                                                         "prefill_s": 0.7}))
    rec.push(("C", "queue_depth", "engine", 3, 1.0, None))


def test_readers_exact_on_a_hand_built_record(record):
    fill(record)
    ctx = ctx_of()
    assert reader("queue_wait_ms")(ctx) == pytest.approx(200.0)
    assert reader("prefill_ms")(ctx) == pytest.approx(600.0)
    # of the two cycles that decoded, one also prefilled
    assert reader("prefill_cycle_share")(ctx) == pytest.approx(50.0)
    # (600 ms of steps - 150 ms of waits) / 1 s
    assert reader("host_busy_share")(ctx) == pytest.approx(45.0)


@pytest.mark.parametrize("name", NAMES)
def test_readers_none_on_an_empty_record(record, name):
    assert reader(name)(ctx_of()) is None


def test_host_busy_share_needs_the_window(record):
    fill(record)
    assert reader("host_busy_share")({}) is None


def test_tiny_cell_traced_reports_the_program_metrics(run_tiny, tiny_root,
                                                      record):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    res = run_tiny("tiny-chat", seed=2**31 + 7, seconds=4.0, trace=True)
    assert res["correct"], res["checks"]
    for name in NAMES:
        assert name in res["metrics"], (name, res["metrics"])
        unit = next(m["unit"] for m in spec["per_layer"] if m["name"] == name)
        assert res["metrics"][name]["unit"] == unit
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["queue_wait_ms"] >= 0 and m["prefill_ms"] > 0
    assert 0 <= m["prefill_cycle_share"] <= 100
    assert 0 < m["host_busy_share"] <= 100
