# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper figure/table.

  fig456_ratios    — Figs. 4-6: compute/params/ratio relative to AlexNet
  fig7_equivalence — Fig. 7: distributed == sequential loss curves
  fig8_scaling     — Fig. 8: strong-scaling speedup (paper's §IV-A model)
  overhead         — §IV-B: runtime-injection overhead (~12% in the paper)
  roofline_table   — EXPERIMENTS.md §Roofline from the dry-run artifacts

Each module's ``run()`` returns [(name, us_per_call, derived), ...]; the
harness prints the combined CSV.  Every module runs in this one process
(a chip belongs to the process that first touches jax); on a CPU host,
fig7 needs placeholder devices given before the run:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.run
"""
import sys
import traceback


def main() -> None:
    from benchmarks import (fig456_ratios, fig7_equivalence, fig8_scaling,
                            overhead, roofline_table, serving)
    modules = [fig456_ratios, fig8_scaling, overhead, fig7_equivalence,
               roofline_table, serving]
    rows = []
    failed = []
    for mod in modules:
        name = mod.__name__.split(".")[-1]
        print(f"\n===== {name} =====", flush=True)
        try:
            rows.extend(mod.run() or [])
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
    print("\n===== CSV =====")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.6g}")
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
