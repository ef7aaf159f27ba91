"""Paper Fig. 7: loss curves of distributed vs sequential training are
identical.

Runs in the benchmark's own process, on the devices that process sees: the
distributed curve is pure data parallelism over 4 devices, the paper's
Fig. 7 setting.  On a CPU host, give the process placeholder devices before
it first imports jax: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import (MeshConfig, OptimizerConfig, RunConfig,
                                ShapeConfig)
from repro.core.transparent import TransparentTrainer
from repro.models import registry

STEPS = 20


def _curves():
    n_dev = jax.device_count()
    if n_dev < 4:
        raise RuntimeError(
            f"fig7 needs at least 4 devices for its DP-4 curve, this process "
            f"sees {n_dev}; on a CPU host set XLA_FLAGS="
            "--xla_force_host_platform_device_count=4 before jax is imported")
    cfg = get_config("stablelm-1.6b", smoke=True)
    bundle = registry.build(cfg)
    rng = np.random.default_rng(7)
    batches = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                      jnp.int32),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                      jnp.int32)}
               for _ in range(STEPS)]
    shape = ShapeConfig("t", "train", 16, 8)
    opt = OptimizerConfig(name="momentum", lr=5e-3)

    def curve(mesh_shape, **kw):
        run = RunConfig(model=cfg, shape=shape,
                        mesh=MeshConfig(shape=mesh_shape,
                                        axis_names=("data", "model"), **kw),
                        optimizer=opt)
        tr = TransparentTrainer(run, bundle.loss_fn, bundle.specs)
        st = tr.init(0)
        out = []
        for b in batches:
            st, m = tr.step(st, b)
            out.append(float(m["loss"]))
        return out

    return curve((1, 1)), curve((4, 1), allreduce="layerwise")


def run():
    seq, dist = _curves()
    dev = max(abs(a - b) for a, b in zip(seq, dist))
    print(f"# Fig7: sequential vs DP-4 loss curves ({STEPS} steps)")
    print("step  sequential  distributed")
    for i, (a, b) in enumerate(zip(seq, dist)):
        print(f"{i:4d}  {a:10.6f}  {b:10.6f}")
    print(f"# max deviation: {dev:.2e}  (paper: 'losses are identical')")
    return [("fig7/max_loss_deviation", 0.0, dev),
            ("fig7/final_loss_seq", 0.0, seq[-1]),
            ("fig7/final_loss_dp4", 0.0, dist[-1])]


if __name__ == "__main__":
    run()
