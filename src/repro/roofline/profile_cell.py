"""Dry-run profiler: lower one cell and print the top FLOP / HBM-byte /
collective contributors with op metadata — the 'profile' used by the §Perf
hypothesis->change->measure loop (no real hardware; the lowered IR is the
profile, per the Pallas dry-run methodology).

  PYTHONPATH=src python -m repro.roofline.profile_cell \
      --arch qwen2.5-14b --shape train_4k --mesh single --mode fsdp

Like ``repro.launch.dryrun``, this lowers for 512 placeholder CPU devices,
so it pins jax to the CPU before jax is imported: on a machine with a TPU
the default backend would be the TPU, which has neither the devices nor
the intent of this tool.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse

from repro.launch.dryrun import lower_cell
from repro.roofline.hlo_parse import analyze_module
from repro.roofline import hw


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--mode", default="paper")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--allreduce-override", default=None)
    ap.add_argument("--rules", default=None)
    ap.add_argument("--q-block", type=int, default=None)
    ap.add_argument("--kv-block", type=int, default=None)
    ap.add_argument("--attn-remat", action="store_true")
    ap.add_argument("--top", type=int, default=14)
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

    lowered, mesh, cfg = lower_cell(args.arch, args.shape, args.mesh,
                                    args.mode, overrides or None)
    compiled = lowered.compile()
    stats = analyze_module(compiled.as_text())
    ma = compiled.memory_analysis()

    print(f"=== {args.arch} {args.shape} {args.mesh} {args.mode} "
          f"overrides={overrides}")
    print(f"compute {stats.flops/hw.PEAK_FLOPS_BF16:10.3f}s   "
          f"memory {stats.hbm_bytes/hw.HBM_BW:10.3f}s   "
          f"collective {stats.wire_bytes_total/hw.ICI_BW_PER_LINK:10.3f}s   "
          f"peak/dev {(ma.argument_size_in_bytes+ma.output_size_in_bytes+ma.temp_size_in_bytes-ma.alias_size_in_bytes)/2**30:.1f} GiB")

    print(f"\n-- top FLOP contributors (of {stats.flops:.3e} total)")
    for c in stats.top_flops(args.top):
        print(f"  {c.flops:9.3e}  x{c.multiplicity:<6.0f} {c.shape:34s} "
              f"{c.meta[-70:]}")
    print(f"\n-- top HBM-byte contributors (of {stats.hbm_bytes:.3e} total)")
    for c in stats.top_bytes(args.top):
        print(f"  {c.bytes:9.3e}  x{c.multiplicity:<6.0f} {c.opcode:22s} "
              f"{c.shape:30s} {c.meta[-60:]}")
    print(f"\n-- top collectives (wire model, of "
          f"{stats.wire_bytes_total:.3e} total)")
    for c in stats.top_collectives(args.top):
        print(f"  {c.wire_bytes:9.3e}  x{c.multiplicity:<6.0f} "
              f"{c.kind:20s} buf={c.result_bytes:.2e} p={c.group_size}")


if __name__ == "__main__":
    main()
