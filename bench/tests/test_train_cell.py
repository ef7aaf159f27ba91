"""The data-parallel training cell at a small size on four CPU devices:
its result line, its check against the sequential fp32 reference, and each
control (int8 products, the exchange left out, half of each batch left
out) judged by the driver's own ``correct``, as
``tools/control_train.py`` judges them on the chip."""
import json
import time

import pytest

from bench import harness
from conftest import BENCH, REPO

TINY_TRAIN = {
    "name": "tiny-train", "source": "test",
    "model": {"hidden_size": 64, "num_hidden_layers": 3,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 128, "vocab_size": 256,
              "partial_rotary_factor": 0.25, "rope_theta": 10000.0,
              "layer_norm_eps": 1e-05, "use_qkv_bias": True,
              "tie_word_embeddings": False},
    "load": {"arch": "stablelm-1.6b", "smoke": True, "mesh": "4x1",
             "dp_mode": "replicated", "allreduce": "layerwise",
             "overrides": {"attn_remat": True}},
    "train": {"optimizer": {"name": "adam", "lr": 1e-3, "beta1": 0.9,
                            "beta2": 0.95, "eps": 1e-8, "grad_clip": 1.0}},
    # the smoke model computes in float32 on the CPU, so the program sits
    # at float32 rounding from the reference (test_program_within_float32_
    # rounding's bounds); the limits sit 5-10x above those bounds and under
    # the int8 control's readings at this size (~1e-3, ~0.09, ~0.16)
    "limits": {"loss_gap": 1e-4, "moment_gap": 0.01, "update_gap": 0.05},
}
TINY_MIX = {
    "driver": "train_dp", "seq_len": 32, "global_batch": 4,
    "tokens": {"dist": "uniform",
               "stream": "Session.train's seeded synthetic tokens"},
    "warmup_steps": 2, "checked_steps": 3, "steps_per_call": 2,
    "trace_s": 1.0,
}


@pytest.fixture(scope="module")
def train_root(tmp_path_factory):
    """A checkout root holding the tiny training cell beside the real
    drivers and readers, as a later PR would add a cell."""
    root = tmp_path_factory.mktemp("train_root")
    bench = root / "bench"
    for sub in ("configs", "traffic"):
        (bench / sub).mkdir(parents=True)
    (bench / "drivers").symlink_to(BENCH / "drivers")
    (bench / "layer_metrics").symlink_to(BENCH / "layer_metrics")
    (bench / "configs" / "tiny-train.json").write_text(json.dumps(TINY_TRAIN))
    (bench / "traffic" / "tiny-4k.json").write_text(json.dumps(TINY_MIX))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        {"name": "tiny-dp4", "config": "tiny-train", "traffic": "tiny-4k",
         "chips": 4, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-dp4"] if "stablelm-dp4" in m["workloads"] \
                else []
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def cell(train_root):
    return harness.Cell("tiny-dp4", train_root, train_root / "bench")


@pytest.fixture(scope="module")
def with_controls(cell):
    return cell.driver.run(cell, 2**33 + 7, 1.0, False, cell.root,
                           time.perf_counter(), control=True)


def test_train_cell_runs_and_is_correct(train_root):
    from bench import run as run_mod
    from test_cells import check_line
    spec = json.loads((train_root / "BENCHMARK.json").read_text())
    res = run_mod.run_cell("tiny-dp4", 5, 1.0, False, root=train_root,
                           bench_dir=train_root / "bench",
                           require_chip=False)
    check_line(res, spec, "tiny-dp4", False)
    assert set(res["metrics"]) == {"setup_s", "serve_tok_s"}
    assert res["metrics"]["serve_tok_s"]["value"] > 0
    assert res["correct"], res["checks"]


def test_train_cell_traced_reports_no_cpu_device_metric(train_root):
    from bench import run as run_mod
    from test_cells import check_line
    spec = json.loads((train_root / "BENCHMARK.json").read_text())
    res = run_mod.run_cell("tiny-dp4", 6, 2.0, True, root=train_root,
                           bench_dir=train_root / "bench",
                           require_chip=False)
    check_line(res, spec, "tiny-dp4", True)
    assert res["metrics"] == {}


def test_program_within_float32_rounding(with_controls):
    """The four-replica program and the sequential reference, both in
    float32 here: what is left is summation order."""
    got = {k: c["value"] for k, c in with_controls["checks"].items()}
    assert got["loss_gap"] < 1e-5 and got["moment_gap"] < 1e-3 \
        and got["update_gap"] < 1e-2, got


@pytest.mark.parametrize("name", ["int8", "shard", "half"])
def test_each_control_fails_where_the_program_passes(cell, with_controls,
                                                     name):
    drv = cell.driver
    assert drv.correct(with_controls["checks"]), with_controls["checks"]
    ctrl = drv.checks_of(with_controls["control"][name],
                         cell.config["limits"])
    assert not drv.correct(ctrl), (name, ctrl)


def test_nan_is_not_correct(cell):
    checks = cell.driver.checks_of(
        {"loss_gap": float("nan"), "moment_gap": 0.0, "update_gap": 0.0},
        cell.config["limits"])
    assert not cell.driver.correct(checks)


def test_train_flops_are_six_per_product_parameter_plus_attention():
    """The cell's model FLOPs: 6 per weight of a matrix product per token
    (the embedding is a gather), counted here from the reference's own
    parameter shapes, plus the causal scores and values; about 2.67
    GFLOP a token at stablelm-2-1.6b's widths and 4 layers."""
    import math

    import jax

    from bench import flops_train
    from bench.reference import dense
    from bench.reference import train as ref
    m = json.loads((BENCH / "configs" / "stablelm-2-1.6b-dp4.json")
                   .read_text())["model"]
    k = ref.dims(m)
    leaves = jax.tree_util.tree_flatten_with_path(
        dense.param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))[0]
    n = sum(math.prod(s) for path, s in leaves
            if path[-1].key.startswith("w") or path[-1].key == "lm_head")
    S, B = 4096, 4
    attn = 3 * 4 * k["H"] * k["hd"] * k["L"] * S * (S + 1) / 2
    got = flops_train.step(k, S, B)
    assert got == pytest.approx(B * (6 * n * S + attn), rel=1e-12)
    assert got / (B * S) == pytest.approx(2.67e9, rel=0.01)


@pytest.mark.parametrize("coll", ["%all-reduce.3", "%psum.150",
                                  "%reduce_scatter.2", "%all-gather.7"])
def test_allreduce_share_counts_only_what_no_other_op_hides(coll):
    """A collective of 40 ns in a 100 ns step, half of it under a fusion
    and all of it under the step's loop: 20 ns exposed, 20%, whether XLA
    or jax named the op; the loop does not hide it."""
    from bench import trace

    reader = harness.load_module(BENCH / "layer_metrics"
                                 / "allreduce_share.py")
    dev = trace.Device(
        ops=[("%while.5", 0.0, 100.0), ("%fusion.1", 0.0, 60.0),
             (coll, 40.0, 80.0)],
        modules=[("jit__local_step(1)", 0.0, 100.0)])
    tr = trace.Trace({0: dev}, [], (0.0, 100.0))
    assert reader.read({"trace": tr, "step_flops": 1.0}) == \
        pytest.approx(20.0)
