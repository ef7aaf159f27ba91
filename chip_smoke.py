#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, through ``repro.api``.

Default phases, on one chip:

  kernels  the per-head paged-attention Pallas kernels (decode and chunked
           prefill) at qwen2.5-14b widths in bf16, compiled for the chip,
           against their jnp oracle;
  serve    qwen2.5-14b at its published widths, cut to SERVE_LAYERS layers,
           fp32 weights drawn from a seed: 8 prompts of 128 tokens, 16 new
           tokens each, default ServeConfig (paged pool, prefix cache,
           speculation, Pallas kernels on);
  train    stablelm-1.6b at its published widths, cut to 2 layers: 5 Adam
           steps on one fixed batch of 4 x 1024 seeded tokens; fitting
           that batch, the loss must fall.

``--four-chips`` runs only what needs the mesh: the train phase
data-parallel over 4 chips against the same global batch on one chip, and
the serve phase on a 2x2 mesh against one chip.

One JSON line per phase gives its compile and wall seconds and its checks.
The last line is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, without that line, when jax finds no TPU or any check fails.
Everything runs in this one process: a chip belongs to the process that
first touches jax.

  python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.data.readers import synthetic_tokens  # noqa: E402
from repro.launch.cli import enable_compile_cache  # noqa: E402
from repro.models import common  # noqa: E402

SEED = 0
SERVE_ARCH, SERVE_LAYERS = "qwen2.5-14b", 4
PROMPTS, PROMPT_LEN, MAX_NEW = 8, 128, 16
TRAIN_ARCH, TRAIN_LAYERS = "stablelm-1.6b", 2
TRAIN_STEPS, SEQ_LEN, GLOBAL_BATCH = 5, 1024, 4
#: kernel vs oracle, bf16 inputs and output: bf16 keeps 8 significant bits
#: (2**-8 ~ 3.9e-3 relative), and the two sides round differently (the
#: kernel accumulates per page, the oracle over the whole row), so allow a
#: few ulps of an O(1) output
KERNEL_ATOL = KERNEL_RTOL = 2e-2
#: DP-4 vs one-device loss per step: the same fp32 master weights and bf16
#: compute, only the gradient sum's association differs (4 partial batch
#: sums + all-reduce vs one batch sum); Adam can turn a last-bit gradient
#: difference into a different step where a gradient is ~0, so allow 1e-3
#: of the loss
TRAIN_RTOL = 1e-3


class CompileClock:
    """Seconds jax has spent tracing, lowering and compiling so far."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


def run_phase(name: str, clock: CompileClock, fn, *args, **kw):
    """Run one phase; print its compile and wall seconds and its checks."""
    c0, t0 = clock.seconds, time.perf_counter()
    result, checks = fn(*args, **kw)
    line = {"phase": name, "compile_s": clock.seconds - c0,
            "wall_s": time.perf_counter() - t0, **checks}
    print(json.dumps(line), flush=True)
    return result, checks


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _param_bytes(session) -> int:
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in common.spec_leaves(session.bundle.specs))


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Phases.  Each returns (result, checks); sizes are arguments so the same
# code runs at small sizes in the tests.
# ---------------------------------------------------------------------------

def phase_kernels(*, KV=8, G=5, hd=128, page_size=16, slots=8, n_table=16,
                  chunk=128, dtype=jnp.bfloat16, seed=SEED):
    """Decode and chunked-prefill kernels vs their jnp oracle on seeded
    random pages.  Raises on a mismatch; reports whether the kernels were
    compiled (not interpreted) and lowered to a TPU custom call."""
    from repro.kernels.paged_attention import ops
    rng = np.random.default_rng(seed)
    H, ps = KV * G, page_size
    P = slots * n_table + 1                       # page 0 = trash
    pages = [jnp.asarray(rng.normal(size=(P, KV, ps, hd)), dtype)
             for _ in range(2)]
    table = jnp.asarray(1 + rng.permutation(P - 1).reshape(slots, n_table),
                        jnp.int32)
    lengths = jnp.asarray(rng.integers(1, n_table * ps + 1, slots), jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, H, hd)), dtype)
    qc = jnp.asarray(rng.normal(size=(chunk, H, hd)), dtype)
    start, n_valid = ps * n_table // 4, chunk * 25 // 32   # padded tail
    cases = (("decode", ops.paged_attention, (q, *pages, table, lengths),
              slots),
             ("prefill", ops.paged_prefill,
              (qc, *pages, table[0], start, n_valid), n_valid))

    checks = {"compiled": not ops._interp(False)}
    for name, op, args, rows in cases:
        got = np.asarray(op(*args, use_kernel=True)[:rows], np.float32)
        want = np.asarray(op(*args, use_kernel=False)[:rows], np.float32)
        err = float(np.max(np.abs(got - want)))
        check(np.all(np.isfinite(got)), f"{name} kernel: non-finite output")
        check(np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL),
              f"{name} kernel vs oracle: max |diff| {err}")
        text = op.lower(*args, use_kernel=True).as_text()
        checks[f"{name}_max_abs_err"] = err
        checks[f"{name}_tpu_custom_call"] = "tpu_custom_call" in text
    return None, checks


def phase_serve(*, arch=SERVE_ARCH, layers=SERVE_LAYERS, smoke=False,
                mesh=None, prompts=PROMPTS, prompt_len=PROMPT_LEN,
                max_new=MAX_NEW, seed=SEED):
    """Serve seeded prompts twice through ``Session.serve``; every request
    must return ``max_new`` in-vocab tokens, the same both times."""
    session = api.load(arch, smoke=smoke, mesh=mesh, seed=seed,
                       num_layers=layers)
    vocab = session.model.vocab_size
    rng = np.random.default_rng(seed)
    batch = rng.integers(0, vocab, (prompts, prompt_len)).tolist()
    t0 = time.perf_counter()
    first = session.serve(batch, max_new=max_new)
    t1 = time.perf_counter()
    again = session.serve(batch, max_new=max_new)
    t2 = time.perf_counter()
    for i, out in enumerate(first):
        check(len(out) == max_new,
              f"request {i}: {len(out)} tokens, want {max_new}")
        check(all(0 <= t < vocab for t in out),
              f"request {i}: token outside the vocabulary")
    check(again == first, "a second identical serve returned other tokens")
    checks = {"arch": arch, "layers": layers, "mesh": mesh,
              "param_bytes": _param_bytes(session),
              "paged_kernel": bool(session.engine.paged_kernel),
              "first_call_s": t1 - t0, "second_call_s": t2 - t1,
              "tokens": prompts * max_new}
    return first, checks


def phase_train(*, arch=TRAIN_ARCH, layers=TRAIN_LAYERS, smoke=False,
                mesh=None, steps=TRAIN_STEPS, seq_len=SEQ_LEN,
                global_batch=GLOBAL_BATCH, seed=SEED):
    """A few steps of ``Session.train`` on one repeated batch; losses must
    be finite and fall.  On a mesh, also counts the all-reduces the
    compiled step holds."""
    session = api.load(arch, smoke=smoke, mesh=mesh, seed=seed,
                       num_layers=layers)
    data = synthetic_tokens(session.model.vocab_size, seq_len,
                            num_samples=global_batch, seed=seed)
    result = session.train(steps=steps, data=data, seq_len=seq_len,
                           global_batch=global_batch)
    losses = result.losses
    check(len(losses) == steps, f"{len(losses)} losses, want {steps}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    checks = {"arch": arch, "layers": layers, "mesh": mesh,
              "param_bytes": _param_bytes(session), "losses": losses}
    if mesh is not None:
        shape = ShapeConfig("smoke", "train", seq_len, global_batch)
        hlo = session.trainer.lower_step(
            session.bundle.train_input_specs(shape)).compile().as_text()
        checks["all_reduces"] = (hlo.count("all-reduce(")
                                 + hlo.count("all-reduce-start("))
    return losses, checks


# ---------------------------------------------------------------------------

def one_chip(clock):
    _, k = run_phase("kernels", clock, phase_kernels)
    check(k["compiled"], "paged kernels ran in the interpreter")
    check(k["decode_tpu_custom_call"] and k["prefill_tpu_custom_call"],
          "paged kernels did not lower to a TPU custom call")
    _, s = run_phase("serve", clock, phase_serve)
    check(s["paged_kernel"], "serving did not select the paged kernels")
    print(json.dumps({"peak_bytes_in_use": _peak_bytes()}), flush=True)
    gc.collect()
    run_phase("train", clock, phase_train)
    print(json.dumps({"peak_bytes_in_use": _peak_bytes()}), flush=True)


def four_chips(clock):
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    one, _ = run_phase("train_1", clock, phase_train)
    gc.collect()
    dp4, t = run_phase("train_dp4", clock, phase_train, mesh="4")
    check(t["all_reduces"] > 0, "DP-4 step holds no all-reduce")
    dev = max(abs(a - b) for a, b in zip(one, dp4))
    print(json.dumps({"train_max_abs_loss_diff": dev,
                      "tolerance": TRAIN_RTOL * max(one)}), flush=True)
    check(dev <= TRAIN_RTOL * max(one),
          f"DP-4 and one-device losses differ by {dev}")
    gc.collect()
    ref, _ = run_phase("serve_1", clock, phase_serve)
    gc.collect()
    got, _ = run_phase("serve_2x2", clock, phase_serve, mesh="2x2")
    same = sum(a == b for r, g in zip(ref, got) for a, b in zip(r, g))
    print(json.dumps({"serve_token_agreement": same / (PROMPTS * MAX_NEW)}),
          flush=True)
    print(json.dumps({"peak_bytes_in_use": [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()]}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh phases")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU (platform {dev.platform!r})")
    enable_compile_cache()
    print(json.dumps({"device_kind": dev.device_kind,
                      "device_count": len(jax.devices()),
                      "jax": jax.__version__}), flush=True)
    clock = CompileClock()
    (four_chips if args.four_chips else one_chip)(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
