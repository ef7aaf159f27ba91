"""Fixtures for the benchmark's own tests (run them by path:
``python -m pytest bench/tests``).  A tiny serving cell is laid out in a
temporary directory as a later PR would add a cell: a configuration file,
a traffic file and an entry in ``BENCHMARK.json``.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

BENCH = REPO / "bench"

TINY_SERVE = {
    "name": "tiny-serve", "source": "test",
    "model": {"hidden_size": 64, "num_hidden_layers": 3,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-06},
    "load": {"arch": "mistral-nemo-12b", "smoke": True, "overrides": {}},
    "serve": {"max_batch": 4, "max_seq_len": 128, "max_queue": 64},
    "limits": {"logit_gap": 1e-3},
}
TINY_CHAT = {
    "driver": "serve_open_loop",
    "arrival": {"process": "poisson", "rate_per_s": 4.0},
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 8, "max": 60},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 24},
    "sampling": {"temperature": 0.8, "top_p": 0.95},
    "greedy_share": 0.5, "warmup_s": 0.5, "trace_s": 1.0,
}


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout root with BENCHMARK.json and a bench dir holding the tiny
    cell's files (drivers and readers are the real ones)."""
    root = tmp_path_factory.mktemp("bench_root")
    bench = root / "bench"
    for sub in ("configs", "traffic"):
        (bench / sub).mkdir(parents=True)
    (bench / "drivers").symlink_to(BENCH / "drivers")
    (bench / "layer_metrics").symlink_to(BENCH / "layer_metrics")
    for sub, name, body in (("configs", "tiny-serve", TINY_SERVE),
                            ("traffic", "tiny-chat", TINY_CHAT)):
        (bench / sub / f"{name}.json").write_text(json.dumps(body))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        {"name": "tiny-chat", "config": "tiny-serve", "traffic": "tiny-chat",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-chat"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def run_tiny(tiny_root):
    """run_cell for a tiny cell on the CPU (the chip check skipped)."""
    from bench import run as run_mod

    def go(workload, seed=11, seconds=2.0, trace=False):
        return run_mod.run_cell(workload, seed, seconds, trace,
                                root=tiny_root, bench_dir=tiny_root / "bench",
                                require_chip=False)
    return go
