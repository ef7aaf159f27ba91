"""What every cell shares: finding its files by name, the device check, the
compile clock, the per-layer readers and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``configs/<config>.json`` and its traffic mix
``traffic/<traffic>.json`` beside this file; the traffic file names the
driver (``drivers/<driver>.py``) that runs it.  A per-layer metric is
``layer_metrics/<name>.py``, whose ``read(ctx)`` returns a number or None.
Nothing here names a cell, a configuration, a mix or a metric.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, Optional

BENCH_DIR = Path(__file__).resolve().parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None):
    """Import one file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload with everything it names, found by name under
    ``bench_dir`` (``root`` holds ``BENCHMARK.json``)."""

    def __init__(self, workload: str, root: Path,
                 bench_dir: Path = BENCH_DIR):
        self.root, self.bench_dir = Path(root), Path(bench_dir)
        self.spec = load_json(self.root / "BENCHMARK.json")
        found = [w for w in self.spec["workloads"] if w["name"] == workload]
        if not found:
            raise SystemExit(f"bench: no workload named {workload!r} in "
                             f"{self.root / 'BENCHMARK.json'}")
        self.workload = found[0]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.config = load_json(
            self.bench_dir / "configs" / f"{self.workload['config']}.json")
        self.traffic = load_json(
            self.bench_dir / "traffic" / f"{self.workload['traffic']}.json")
        self.driver = load_module(
            self.bench_dir / "drivers" / f"{self.traffic['driver']}.py")

    def _applies(self, metric: dict) -> bool:
        wls = metric.get("workloads")
        return wls is None or self.name in wls

    def end_to_end(self) -> list:
        return [m for m in self.spec["end_to_end"] if self._applies(m)]

    def per_layer(self) -> list:
        return [m for m in self.spec["per_layer"] if self._applies(m)]

    def read_layers(self, ctx: dict) -> Dict[str, dict]:
        """Run each per-layer reader of this cell; a reader that finds
        nothing to read returns None and its metric is left out."""
        out = {}
        for m in self.per_layer():
            mod = load_module(self.bench_dir / "layer_metrics"
                              / f"{m['name']}.py")
            value = mod.read(ctx)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


# ---------------------------------------------------------------------------
# Device, compile cache, compile clock
# ---------------------------------------------------------------------------

def require_chips(n: int) -> None:
    """Exit non-zero, printing no result, unless jax sees at least ``n``
    TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench: this cell needs {n} TPU chip(s); jax found "
                 f"{len(devs)} {devs[0].platform} device(s)")


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compile cache at ``<root>/.jax_cache`` (a fixed
    path inside the checkout), or where ``JAX_COMPILATION_CACHE_DIR``
    points when it is set."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Counts jax's compile events and their seconds (after chip_smoke.py's
    clock): set-up reads the seconds, the window reads the count."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.traced: list = []            # names of the functions traced
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @property
    def events(self) -> int:
        return len(self.traced)

    def _on_event(self, name, secs, fun_name=None, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("jaxpr_trace_duration"):
                self.traced.append(fun_name)


def device_info(devices) -> dict:
    """Platform, kind, count and the peak bytes in use on the fullest
    chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def peaks_for(kind: str, strict: bool = True) -> Optional[dict]:
    """The chip's published peaks; a device missing from the table is an
    error, not a default (``strict=False``: None, for CPU test runs)."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        if not strict:
            return None
        raise KeyError(f"bench/peaks.json has no entry for device kind "
                       f"{kind!r}")
    return table[kind]


def note(obj) -> None:
    """An earlier line on standard output (the result line comes last)."""
    print(json.dumps(obj), flush=True)


def print_checks(checks: Dict[str, dict]) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
