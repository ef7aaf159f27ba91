"""Cells found by name, the result line's form, and the refusal to run
without a chip."""
import json
import math
import os
import subprocess
import sys


from bench import harness
from conftest import REPO


def check_line(res, spec, workload, trace):
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert isinstance(res["correct"], bool)
    assert res["attempted"] > 0 and res["failed"] >= 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    group = spec["per_layer"] if trace else spec["end_to_end"]
    names = {m["name"]: m["unit"] for m in group
             if workload in m.get("workloads", [workload])}
    for name, m in res["metrics"].items():
        assert names[name] == m["unit"]
        assert math.isfinite(m["value"])
    if not trace:
        assert set(res["metrics"]) == set(names)
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(res)


def test_found_by_name(tiny_root):
    cell = harness.Cell("tiny-chat", tiny_root, tiny_root / "bench")
    assert cell.config["name"] == "tiny-serve"
    assert cell.traffic["driver"] == "serve_open_loop"
    assert cell.driver.__name__.endswith("serve_open_loop")


def test_serve_cell_runs_and_is_correct(run_tiny, tiny_root):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    res = run_tiny("tiny-chat", seed=2**33 + 1)
    check_line(res, spec, "tiny-chat", False)
    assert res["correct"], res["checks"]
    assert res["checks"]["checked_tokens"]["value"] > 0


def test_serve_cell_traced_reports_no_cpu_device_metric(run_tiny, tiny_root):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    res = run_tiny("tiny-chat", seed=3, trace=True)
    check_line(res, spec, "tiny-chat", True)
    # the CPU has no entry in the peaks table and no device trace: every
    # device-trace metric is left out, never written from a CPU run
    device_metrics = {m["name"] for m in spec["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(res["metrics"])


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"], REPO)
        assert cell.config["name"] == w["config"]
    for m in spec["per_layer"]:
        assert (REPO / "bench" / "layer_metrics" / f"{m['name']}.py").is_file()


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nemo-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_bare_checkout_refuses(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ (no program)
    exits non-zero and prints no result."""
    import shutil
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nemo-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_warm_up_leaves_nothing_to_compile(tiny_root):
    """After the warm-up, requests that speculate in any number of slots
    at once, and have their drafts accepted, trace no program again."""
    import numpy as np
    cell = harness.Cell("tiny-chat", tiny_root, tiny_root / "bench")
    drv = cell.driver
    session, engine = drv.build(cell.config, 5)
    drv.warm_up(engine, cell.traffic, 256, 5)
    # one compiled verify per power-of-two row count up to the 4 slots
    assert engine._verify._cache_size() == 3
    clock = harness.CompileClock()
    rng = np.random.default_rng(0)
    for k in (1, 3, 4):
        for _ in range(k):
            engine.submit(rng.integers(0, 256, 16).tolist(), 44)
        engine.run()
    assert engine.metrics.accepted_tokens > 0
    assert clock.traced == [], clock.traced


def test_every_request_due_in_the_window_is_attempted(tiny_root,
                                                      monkeypatch):
    """A host that is inside a slow step when the window closes still
    submits, and counts, every request that fell due before the close."""
    import time
    from bench import traffic
    from repro.serving.engine import ServingEngine
    cell = harness.Cell("tiny-chat", tiny_root, tiny_root / "bench")
    drv = cell.driver
    session, engine = drv.build(cell.config, 31)
    drv.warm_up(engine, cell.traffic, 256, 31)
    step = ServingEngine.step

    def slow(self, *a, **k):
        time.sleep(0.5)
        return step(self, *a, **k)

    monkeypatch.setattr(ServingEngine, "step", slow)
    reqs = traffic.generate(cell.traffic, 2.0, 31, 256)
    w = drv.open_loop(engine, cell.traffic, reqs, 2.0)
    warm = cell.traffic["warmup_s"]
    assert len(w.due_in) == sum(warm <= r.due_s < warm + 2.0 for r in reqs)
    assert max(w.submit_at[i] for i in w.due_in) > w.t_close
