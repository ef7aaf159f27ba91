"""Shared CLI plumbing for the launch drivers (the flag builder behind
``repro.api``).

Every driver used to hand-roll the same argparse soup: ``--arch/--smoke``,
``--mesh DxM`` parsing, and the XLA placeholder-device bootstrap that must
happen *before* the first jax import.  They now live here exactly once;
each driver adds its workload-specific flags and asks for a ``Session``.

Import discipline: this module must stay importable without touching jax
device state — ``bootstrap_devices`` only sets XLA_FLAGS, and Session
construction defers all device work to first use (see repro.api.session).
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Optional

from repro.api.session import parse_mesh  # the single --mesh parser

#: the checkout's root, resolved from this file (src/repro/launch/cli.py)
_REPO_ROOT = Path(__file__).resolve().parents[3]


def add_session_flags(ap: argparse.ArgumentParser, *,
                      arch_default: str = "qwen2.5-14b",
                      mesh_help: Optional[str] = None):
    """The flags every Session-backed driver shares."""
    ap.add_argument("--arch", default=arch_default)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-sized config variant")
    ap.add_argument("--mesh", default=None, type=_mesh_arg,
                    help=mesh_help or
                    "device mesh 'D', 'DxM' or 'PxDxM' (e.g. 2x2 = 2-way "
                    "data x 2-way model; default: single device)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N placeholder CPU devices (0 = mesh size "
                         "when --mesh is set and jax is not yet imported)")
    return ap


def _mesh_arg(spec: str):
    try:
        return parse_mesh(spec)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None


def bootstrap_devices(args):
    """Ensure enough placeholder CPU devices exist for ``args.mesh``.

    Must run before the first jax import: jax locks the device count on
    first initialization (same bootstrap all drivers used to copy-paste).
    Appends to an existing XLA_FLAGS (e.g. a user's --xla_dump_to) unless
    it already pins a device count of its own.
    """
    n = args.devices or (args.mesh.num_devices if args.mesh else 0)
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, so a later run of the same checkout finds its entries.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(_REPO_ROOT / ".jax_cache"))


def make_session(args, **load_kw):
    """Build the Session a driver runs against (import deferred past
    ``bootstrap_devices`` on purpose)."""
    bootstrap_devices(args)
    enable_compile_cache()
    from repro import api
    return api.load(args.arch, smoke=args.smoke, mesh=args.mesh,
                    seed=args.seed, **load_kw)
