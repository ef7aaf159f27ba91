"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a *described* ``v5e:2x2``
topology, and refuses what the chip would refuse (block shapes the TPU
cannot tile, too much VMEM) — which interpret-mode tests cannot see.  The
widths are qwen2.5-14b's (KV 8, G 5, hd 128) for the per-head kernels and
deepseek-v2-lite's (H 16, latent rank 512, rope 64) for MLA.  The
stablelm-dp4 benchmark cell's data-parallel train step compiles for the
four chips at its published widths and fits their memory.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.paged_attention import kernel as K

SLOTS, KV, G, HD = 8, 8, 5, 128               # qwen2.5-14b
N_TABLE, WINDOW = 16, 256
MLA_H, MLA_R, MLA_RP = 16, 512, 64             # deepseek-v2-lite
BF16, I8, I32 = jnp.bfloat16, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _pages(ps, dtype, quantized, slots=SLOTS, n_table=N_TABLE):
    """(k, v[, k_scale, v_scale]) head-major page shapes."""
    P = slots * n_table + 1
    data = [((P, KV, ps, HD), I8 if quantized else dtype)] * 2
    scales = [((P, KV, 1, ps), BF16)] * 2 if quantized else []
    return data, scales


def _decode(ps, dtype, quantized, window, slots=SLOTS, g=G, n_table=N_TABLE):
    data, scales = _pages(ps, dtype, quantized, slots, n_table)
    n = WINDOW // ps if window else n_table
    shapes = ([((slots, KV, g, HD), dtype)] + data
              + [((slots, n), I32), ((slots,), I32)] + scales)

    def fn(q, k, v, pt, ln, *sc):
        ks, vs = sc or (None, None)
        return K.paged_attention_kernel(q, k, v, pt, ln, window=window,
                                        k_scale=ks, v_scale=vs)
    return fn, shapes


def _prefill(S, ps, dtype, quantized, window):
    data, scales = _pages(ps, dtype, quantized)
    q = [((S, KV, G, HD), dtype)]
    meta = [((2,), I32)]
    if not window:
        def fn(q, k, v, pt, mt, *sc):
            ks, vs = sc or (None, None)
            return K.paged_prefill_kernel(q, k, v, pt, mt, k_scale=ks,
                                          v_scale=vs)
        return fn, q + data + [((N_TABLE,), I32)] + meta + scales
    chunk = [((S, KV, HD), dtype)] * 2

    def fn(q, k, v, ck, cv, pt, mt, *sc):
        ks, vs = sc or (None, None)
        return K.paged_ring_prefill_kernel(q, k, v, ck, cv, pt, mt,
                                           window=window, k_scale=ks,
                                           v_scale=vs)
    return fn, q + data + chunk + [((WINDOW // ps,), I32)] + meta + scales


def _mla_decode():
    P = SLOTS * N_TABLE + 1
    fn = functools.partial(K.paged_mla_kernel, scale=(128 + MLA_RP) ** -0.5)
    return fn, [((SLOTS, MLA_H, MLA_R), BF16), ((SLOTS, MLA_H, MLA_RP), BF16),
                ((P, 16, MLA_R), BF16), ((P, 16, MLA_RP), BF16),
                ((SLOTS, N_TABLE), I32), ((SLOTS,), I32)]


def _mla_prefill(S):
    P = N_TABLE + 1
    fn = functools.partial(K.paged_mla_prefill_kernel,
                           scale=(128 + MLA_RP) ** -0.5)
    return fn, [((S, MLA_H, MLA_R), BF16), ((S, MLA_H, MLA_RP), BF16),
                ((P, 16, MLA_R), BF16), ((P, 16, MLA_RP), BF16),
                ((N_TABLE,), I32), ((2,), I32)]


def _flash(S=1024):
    fn = functools.partial(flash_attention_bhsd, causal=True)
    return fn, [((G * KV, S, HD), BF16), ((KV, S, HD), BF16),
                ((KV, S, HD), BF16)]


# (id, builder) — builders return (fn, [(shape, dtype), ...]).  Page sizes
# are the sublane tile of each storage dtype (8 f32 / 16 bf16 / 32 int8
# rows), and, in the "-page16"/"-page8" cases, below it: a prefill page
# block (1, 1, ps, hd), like a decode page copy [KV, ps, hd], spans the
# page's whole last two dims, so the TPU takes any page size — int8 pools
# at ServeConfig's default page_size=16 included
CASES = [
    ("decode-bf16", lambda: _decode(16, BF16, False, 0)),
    ("decode-f32", lambda: _decode(16, jnp.float32, False, 0)),
    ("decode-int8", lambda: _decode(32, BF16, True, 0)),
    ("decode-ring-bf16", lambda: _decode(16, BF16, False, WINDOW)),
    ("decode-ring-int8", lambda: _decode(32, BF16, True, WINDOW)),
    ("prefill-bf16", lambda: _prefill(128, 16, BF16, False, 0)),
    ("prefill-bf16-verify-width", lambda: _prefill(5, 16, BF16, False, 0)),
    ("prefill-bf16-multiblock", lambda: _prefill(512, 16, BF16, False, 0)),
    ("prefill-int8", lambda: _prefill(128, 32, BF16, True, 0)),
    ("ring-prefill-bf16", lambda: _prefill(128, 16, BF16, False, WINDOW)),
    ("ring-prefill-int8", lambda: _prefill(128, 32, BF16, True, WINDOW)),
    ("decode-int8-page16", lambda: _decode(16, BF16, True, 0)),
    ("decode-ring-int8-page16", lambda: _decode(16, BF16, True, WINDOW)),
    ("prefill-int8-page16", lambda: _prefill(128, 16, BF16, True, 0)),
    ("ring-prefill-int8-page16",
     lambda: _prefill(128, 16, BF16, True, WINDOW)),
    ("decode-bf16-page8", lambda: _decode(8, BF16, False, 0)),
    # the nemo-chat benchmark cell's decode: 32 slots, G 4, a 256-page
    # table of 16-token fp32 pages (16 pages a block, 16 blocks)
    ("decode-f32-nemo-cell",
     lambda: _decode(16, jnp.float32, False, 0, slots=32, g=4, n_table=256)),
    ("mla-decode", _mla_decode),
    ("mla-prefill", lambda: _mla_prefill(128)),
    ("flash", _flash),
]


@pytest.mark.parametrize("build", [b for _, b in CASES],
                         ids=[i for i, _ in CASES])
def test_kernel_compiles_for_v5e(one_chip, no_compile_cache, build):
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()



#: one v5e chip's HBM (bench/peaks.json)
HBM_BYTES = 16e9


def test_dp4_train_step_compiles_for_v5e(topo, no_compile_cache):
    """The stablelm-dp4 cell's step: stablelm-2-1.6b at its published
    widths, 4 layers, replicated over a 4x1 mesh with the layerwise
    all-reduce and Adam, one 4096-token sequence per chip."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.configs.base import (MeshConfig, OptimizerConfig, RunConfig,
                                    ShapeConfig)
    from repro.core.transparent import TransparentTrainer
    from repro.models import registry
    cfg = get_config("stablelm-1.6b").replace(num_layers=4, attn_remat=True)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    run = RunConfig(model=cfg, shape=ShapeConfig("c", "train", 4096, 4),
                    mesh=MeshConfig(shape=(4, 1),
                                    axis_names=("data", "model")),
                    optimizer=OptimizerConfig(name="adam", lr=1e-3))
    bundle = registry.build(cfg)
    trainer = TransparentTrainer.from_bundle(run, bundle, mesh=mesh)
    compiled = trainer.lower_step(
        bundle.train_input_specs(run.shape)).compile()
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert held < HBM_BYTES, held
    hlo = compiled.as_text()
    assert "all-reduce" in hlo and "/allreduce/" in hlo


def test_sampler_compiles_without_sort(one_chip, no_compile_cache):
    """The nemo-chat cell's sampler, 32 slots of a 131072-token
    vocabulary: the top-k and top-p cutoffs come from threshold searches,
    so the program holds no sort.  The sampling parameters are operands,
    so this one program serves top-p rows and top-k rows alike: its
    three conditionals (sampled or greedy, top-k, top-p) are all here."""
    import re

    from repro.serving.sampling import PACKED_WIDTH, sample_tokens
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (((32, 131072), jnp.float32),
                         ((32, PACKED_WIDTH), I32), ((32,), I32))]
    hlo = jax.jit(sample_tokens).lower(*args).compile().as_text()
    assert not re.search(r"\bsort\b", hlo)
    assert len(re.findall(r"\bconditional\(", hlo)) >= 3
