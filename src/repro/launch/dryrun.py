"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh and record memory / cost / collective analyses.

This is the one driver that does NOT construct a ``repro.api.Session``: it
never executes a step — it lowers the same building blocks a Session owns
(registry bundles, ``TransparentTrainer.from_bundle``, the serve decode
contracts) against 512 placeholder devices to predict production memory /
cost.  User-facing train/serve entrypoints live behind ``repro.api`` and
``launch/cli.py``; the ``--mesh`` flag here selects the *production* preset
(single: 16x16, multi: 2x16x16), not the free-form ``DxM`` spec.

The environment lines below MUST run before jax is imported: jax locks the
device count on first initialization, and the production meshes need 512
placeholder CPU devices.  The backend is pinned to the CPU because those
placeholders are CPU devices — on a machine with a TPU, jax would otherwise
pick the TPU, see its few chips, and hold them for this whole process.
Smoke tests and benchmarks do NOT import this module (they see 1 device).

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every runnable cell
  python -m repro.launch.dryrun --list           # enumerate cells

One JSON per cell lands in results/dryrun/; existing files are skipped
(incremental).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, get_shape
from repro.configs.base import (MeshConfig, ModelConfig, OptimizerConfig,
                                RunConfig, ShapeConfig, MULTI_POD, SINGLE_POD)
from repro.launch import sharding as shrules
from repro.launch.mesh import build_mesh, dp_size, make_production_mesh, model_size
from repro.models import common, registry
from repro.roofline import hw
from repro.roofline.hlo_parse import analyze_module

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# train-cell variants: the paper-faithful baseline and the beyond-paper modes
TRAIN_MODES = {
    "paper": dict(dp_mode="replicated", allreduce="layerwise"),
    "zero1": dict(dp_mode="replicated", allreduce="reduce_scatter"),
    "fsdp": dict(dp_mode="fsdp", allreduce="layerwise"),
}
DEFAULT_TRAIN_MODES = ("paper", "fsdp")


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell (deliverable)."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    bundle = registry.build(cfg)
    if shape.kind == "train":
        return bundle.train_input_specs(shape)
    if shape.kind == "prefill":
        return bundle.prefill_input_specs(shape)
    return {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            "state": bundle.decode_state_specs(shape)}


def runnable(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False                   # pure full-attention: documented skip
    return True


def all_cells(train_modes=DEFAULT_TRAIN_MODES):
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        for shape_name in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            shape = get_shape(shape_name)
            if not runnable(cfg, shape):
                continue
            for mesh_name in ("single", "multi"):
                if shape.kind == "train":
                    for mode in train_modes:
                        yield (arch, shape_name, mesh_name, mode)
                else:
                    yield (arch, shape_name, mesh_name, "serve")


def _mesh_cfg(mesh_name: str, **overrides) -> MeshConfig:
    base = SINGLE_POD if mesh_name == "single" else MULTI_POD
    import dataclasses
    return dataclasses.replace(base, **overrides)


def _bf16_param_structs(bundle):
    def one(s):
        dt = jnp.dtype(s.dtype)
        if jnp.issubdtype(dt, jnp.floating):
            dt = jnp.dtype(jnp.bfloat16)
        return jax.ShapeDtypeStruct(s.shape, dt)
    return common.tree_map_specs(
        lambda s: one(s), bundle.specs)


def _serve_param_rules(cfg, mesh_cfg):
    """Model-axis TP; switch to 2-D (embed->data) when bf16 weights would not
    fit model-sharded (e.g. mixtral-8x22b: 262 GB bf16 / 16 > HBM)."""
    n_params = registry.count_params(cfg)
    per_chip = 2.0 * n_params / model_size(mesh_cfg)
    rules = common.rules_for(mesh_cfg, cfg)
    if per_chip > 0.6 * hw.HBM_BYTES:
        rules = dict(rules)
        rules["embed"] = "data"
    return rules


def lower_cell(arch: str, shape_name: str, mesh_name: str, mode: str,
               overrides: dict = None):
    """overrides (hillclimb knobs): microbatch:int, remat:str,
    allreduce:str, rules:{logical->mesh axis}."""
    ov = overrides or {}
    cfg = get_config(arch)
    if ov.get("remat"):
        cfg = cfg.replace(remat=ov["remat"])
    if ov.get("q_block"):
        cfg = cfg.replace(attn_q_block=int(ov["q_block"]))
    if ov.get("kv_block"):
        cfg = cfg.replace(attn_kv_block=int(ov["kv_block"]))
    if ov.get("attn_remat"):
        cfg = cfg.replace(attn_remat=True)
    shape = get_shape(shape_name)
    bundle = registry.build(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rules_override = tuple(sorted(ov.get("rules", {}).items()))

    if shape.kind == "train":
        from repro.core.transparent import TransparentTrainer
        kw = dict(TRAIN_MODES[mode])
        if ov.get("allreduce"):
            kw["allreduce"] = ov["allreduce"]
        mesh_cfg = _mesh_cfg(mesh_name, rules_override=rules_override, **kw)
        run = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg,
                        optimizer=OptimizerConfig(name="adam"),
                        microbatch=int(ov.get("microbatch", 2)))
        trainer = TransparentTrainer.from_bundle(run, bundle, mesh=mesh)
        return trainer.lower_step(bundle.train_input_specs(shape)), mesh, cfg

    mesh_cfg = _mesh_cfg(mesh_name, rules_override=rules_override)
    dp_axes = mesh_cfg.dp_axes
    dp = dp_size(mesh_cfg)
    msize = model_size(mesh_cfg)
    rules = _serve_param_rules(cfg, mesh_cfg)
    pshard = common.logical_to_mesh(bundle.specs, mesh, rules)
    pstructs = jax.tree.map(
        lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype, sharding=sh),
        _bf16_param_structs(bundle), pshard)

    if shape.kind == "prefill":
        inputs = bundle.prefill_input_specs(shape)
        ispecs = shrules.serve_input_pspecs(inputs, dp_axes=dp_axes, dp_total=dp)
        istructs = shrules.with_shardings(inputs, ispecs, mesh)

        def _prefill(params, inp):
            with common.activation_batch_axes(dp_axes):
                return bundle.prefill_fn(params, **inp)
        fn = jax.jit(_prefill)
        return fn.lower(pstructs, istructs), mesh, cfg

    # decode
    state = bundle.decode_state_specs(shape)
    sspecs = shrules.serve_state_pspecs(state, dp_axes=dp_axes, dp_total=dp,
                                        model_size=msize)
    sstructs = shrules.with_shardings(state, sspecs, mesh)
    tok = {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)}
    tspecs = shrules.serve_input_pspecs(tok, dp_axes=dp_axes, dp_total=dp)
    tstructs = shrules.with_shardings(tok, tspecs, mesh)
    state_sh = jax.tree.map(
        lambda sp: jax.sharding.NamedSharding(mesh, sp), sspecs)

    def _decode(params, tokens, state):
        with common.activation_batch_axes(dp_axes):
            return bundle.decode_fn(params, tokens, state)
    fn = jax.jit(_decode, donate_argnums=(2,),
                 out_shardings=(None, state_sh))
    return fn.lower(pstructs, tstructs["tokens"], sstructs), mesh, cfg


def analyze_cell(arch: str, shape_name: str, mesh_name: str, mode: str,
                 save_hlo: bool = False, overrides: dict = None):
    t0 = time.time()
    lowered, mesh, cfg = lower_cell(arch, shape_name, mesh_name, mode,
                                    overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    stats = analyze_module(hlo)
    shape = get_shape(shape_name)
    n_dev = int(np.prod(mesh.devices.shape))

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "mode": mode,
        "devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
        },
        "xla_cost": {"flops": ca.get("flops", 0.0),
                     "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "hlo_stats": {
            "dot_flops": stats.dot_flops,
            "conv_flops": stats.conv_flops,
            "hbm_bytes": stats.hbm_bytes,
            "wire_bytes": stats.wire_bytes_total,
            "collectives": stats.collective_summary(),
            "while_trip_counts": stats.while_trip_counts[:50],
        },
        "model_flops_global": registry.model_flops(cfg, shape),
        "params_total": registry.count_params(cfg),
        "params_active": registry.count_params(cfg, active_only=True),
        "hlo_bytes_len": len(hlo),
        "overrides": overrides or {},
    }
    if save_hlo:
        import gzip
        with gzip.open(RESULTS_DIR /
                       f"{_cell_id(arch, shape_name, mesh_name, mode)}.hlo.gz",
                       "wt") as f:
            f.write(hlo)
    return rec


def _cell_id(arch, shape, mesh, mode, tag=""):
    base = f"{arch}__{shape}__{mesh}__{mode}"
    if tag:
        base += f"__{tag}"
    return base.replace("/", "_")


def run_one(arch, shape, mesh, mode, save_hlo=False, overrides=None, tag=""):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out = RESULTS_DIR / f"{_cell_id(arch, shape, mesh, mode, tag)}.json"
    try:
        rec = analyze_cell(arch, shape, mesh, mode, save_hlo, overrides)
        rec["ok"] = True
        if tag:
            rec["tag"] = tag
    except Exception as e:
        rec = {"arch": arch, "shape": shape, "mesh": mesh, "mode": mode,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    out.write_text(json.dumps(rec, indent=1))
    status = "OK " if rec.get("ok") else "ERR"
    print(f"[{status}] {out.name}  compile={rec.get('compile_s', '-')}s",
          flush=True)
    return rec.get("ok", False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--mode", default=None,
                    help="train: paper|zero1|fsdp; serve cells ignore this")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    # hillclimb knobs (single-cell runs)
    ap.add_argument("--tag", default="", help="suffix for the result file")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--remat", default=None, choices=["none", "full", "dots"])
    ap.add_argument("--allreduce-override", default=None)
    ap.add_argument("--rules", default=None,
                    help="logical=mesh axis overrides, e.g. "
                         "'vocab_table=model,embed=data'")
    ap.add_argument("--q-block", type=int, default=None)
    ap.add_argument("--kv-block", type=int, default=None)
    ap.add_argument("--attn-remat", action="store_true")
    args = ap.parse_args()

    overrides = {}
    if args.microbatch is not None:
        overrides["microbatch"] = args.microbatch
    if args.remat:
        overrides["remat"] = args.remat
    if args.allreduce_override:
        overrides["allreduce"] = args.allreduce_override
    if args.q_block:
        overrides["q_block"] = args.q_block
    if args.kv_block:
        overrides["kv_block"] = args.kv_block
    if args.attn_remat:
        overrides["attn_remat"] = True
    if args.rules:
        overrides["rules"] = {
            k: (v if v not in ("None", "none", "") else None)
            for k, v in (kv.split("=") for kv in args.rules.split(","))}

    if args.list or args.all:
        cells = list(all_cells())
        if args.list:
            for c in cells:
                print("%s %s %s %s" % c)
            print(f"total: {len(cells)} lowering cells")
            return
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        n_ok = n_err = n_skip = 0
        for (arch, shape, mesh, mode) in cells:
            out = RESULTS_DIR / f"{_cell_id(arch, shape, mesh, mode)}.json"
            if out.exists() and not args.force:
                prev = json.loads(out.read_text())
                if prev.get("ok"):
                    n_skip += 1
                    continue
            ok = run_one(arch, shape, mesh, mode, args.save_hlo)
            n_ok += int(ok)
            n_err += int(not ok)
        print(f"done: ok={n_ok} err={n_err} skipped={n_skip}")
        return

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    shape = get_shape(args.shape)
    mode = args.mode or ("paper" if shape.kind == "train" else "serve")
    ok = run_one(args.arch, args.shape, args.mesh, mode, args.save_hlo,
                 overrides=overrides or None, tag=args.tag)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
