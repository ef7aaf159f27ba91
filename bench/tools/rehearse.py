#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e (no chip) and print
what ``memory_analysis`` says each needs on one chip.

  JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py --config nemo-12b-serve \
      [--layers N] [--slots S]

A serving configuration compiles the engine's fused decode scan (all
slots, Pallas decode kernel) and a whole-prompt paged prefill of the
longest bucket.  Nothing runs, so no time is measured here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness  # noqa: E402


def gb(x):
    return round(x / 1e9, 3)


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments_gb": gb(m.argument_size_in_bytes),
            "outputs_gb": gb(m.output_size_in_bytes),
            "temporaries_gb": gb(m.temp_size_in_bytes),
            "aliased_gb": gb(m.alias_size_in_bytes),
            "total_gb": gb(m.argument_size_in_bytes + m.output_size_in_bytes
                           + m.temp_size_in_bytes - m.alias_size_in_bytes)}


def model_cfg(cfg: dict, layers):
    from repro.configs import get_config
    over = dict(cfg["load"].get("overrides", {}))
    if layers:
        over["num_layers"] = layers
    return get_config(cfg["load"]["arch"]).replace(**over)


def serve(cfg: dict, topo, layers, slots) -> dict:
    import repro.kernels.paged_attention.ops as ops
    from repro.models import common, registry
    ops._interp = lambda interpret: interpret      # compile, do not interpret
    m = model_cfg(cfg, layers)
    bundle = registry.build(m)
    dev = SingleDeviceSharding(topo.devices[0])
    sc = cfg["serve"]
    slots = slots or sc["max_batch"]
    ps = sc.get("page_size", 16)
    n_table = -(-sc["max_seq_len"] // ps)
    pages_n = sc.get("num_pages") or slots * n_table + 1
    kv = (m.num_layers, pages_n, m.num_kv_heads, ps, m.resolved_head_dim)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)
    params = jax.tree.map(lambda s: sds(s.shape, jnp.dtype(s.dtype)),
                          bundle.specs, is_leaf=common.is_spec)
    pages = {"k": sds(kv, jnp.bfloat16), "v": sds(kv, jnp.bfloat16)}

    def decode(params, toks, pages, table, pos):
        return bundle.paged_decode_fn(
            params, toks, {"pages": pages, "page_table": table, "pos": pos},
            use_pallas=True)

    def prefill(params, toks, pages, table, start, n_valid):
        return bundle.paged_prefill_fn(
            params, toks, {"pages": pages, "page_table": table,
                           "start": start, "n_valid": n_valid},
            use_pallas=True)

    i32 = jnp.int32
    out = {"layers": m.num_layers, "slots": slots, "pages": pages_n,
           "param_gb": gb(sum(s.size * 4 for s in jax.tree.leaves(params))),
           "kv_pool_gb": gb(2 * 2 * jnp.prod(jnp.array(kv)).item())}
    def decode_scan(params, toks, pages, table, pos):
        # the engine's fused scan over ServeConfig.decode_steps
        def body(carry, _):
            toks, pos, pages = carry
            logits, pages = decode(params, toks[:, None], pages, table, pos)
            return (jnp.argmax(logits, -1).astype(jnp.int32), pos + 1,
                    pages), toks
        (toks, _, pages), stack = jax.lax.scan(
            body, (toks, pos, pages), None, length=sc.get("decode_steps", 4))
        return stack, pages

    c = jax.jit(decode_scan, donate_argnums=(2,)).lower(
        params, sds((slots,), i32), pages, sds((slots, n_table), i32),
        sds((slots,), i32)).compile()
    out["decode_scan"] = mem(c)
    out["decode_kernel"] = "tpu_custom_call" in c.as_text()
    bucket = 1 << (sc["max_seq_len"] - 1).bit_length()
    c = jax.jit(prefill, donate_argnums=(2,)).lower(
        params, sds((1, bucket), i32), pages, sds((n_table,), i32),
        sds((), i32), sds((), i32)).compile()
    out[f"prefill_{bucket}"] = mem(c)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--slots", type=int, default=0)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = harness.load_json(harness.BENCH_DIR / "configs"
                            / f"{args.config}.json")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    out = serve(cfg, topo, args.layers, args.slots)
    print(json.dumps({"config": args.config, **out}))


if __name__ == "__main__":
    main()
