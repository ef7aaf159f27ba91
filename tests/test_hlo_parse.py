"""Roofline HLO analyzer: scan trip-count correction + collective capture
(the calibration that justifies not trusting cost_analysis — DESIGN.md §7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_parse import analyze_module

P = jax.sharding.PartitionSpec


def test_scan_flops_exact():
    D, L, B = 128, 8, 4
    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)

    def scanned(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None
        return jax.lax.scan(body, x, w)[0]

    compiled = jax.jit(scanned).lower(w, x).compile()
    stats = analyze_module(compiled.as_text())
    assert stats.dot_flops == pytest.approx(2 * B * D * D * L, rel=1e-6)
    assert L in stats.while_trip_counts
    # XLA's own analysis undercounts by exactly the trip count
    ca = compiled.cost_analysis()
    assert ca["flops"] == pytest.approx(stats.dot_flops / L, rel=0.2)


def test_nested_scan_multiplicity():
    D, L1, L2 = 64, 3, 5
    w = jax.ShapeDtypeStruct((L1, L2, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((2, D), jnp.float32)

    def fn(w, x):
        def outer(x, wo):
            def inner(x, wi):
                return x @ wi, None
            return jax.lax.scan(inner, x, wo)[0], None
        return jax.lax.scan(outer, x, w)[0]

    stats = analyze_module(jax.jit(fn).lower(w, x).compile().as_text())
    assert stats.dot_flops == pytest.approx(2 * 2 * D * D * L1 * L2, rel=1e-6)


def test_collectives_captured_with_groups(mesh42):
    def step(x):
        return jax.lax.pmean(x, "data")

    sm = jax.shard_map(step, mesh=mesh42, in_specs=P("data"), out_specs=P(),
                       check_vma=False)
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    stats = analyze_module(jax.jit(sm).lower(x).compile().as_text())
    kinds = {c.kind for c in stats.collectives}
    assert "all-reduce" in kinds
    ar = [c for c in stats.collectives if c.kind == "all-reduce"][0]
    assert ar.group_size == 4
    # per-device buffer: [2,128] f32 = 1024B; ring wire = 2*N*(p-1)/p
    assert ar.result_bytes == 2 * 128 * 4
    assert ar.wire_bytes == pytest.approx(2 * 1024 * 3 / 4)


def test_collective_inside_scan_multiplied(mesh42):
    L = 6

    def step(w, x):
        def body(x, wl):
            y = x @ wl
            return jax.lax.pmean(y, "data"), None
        return jax.lax.scan(body, x, w)[0]

    sm = jax.shard_map(step, mesh=mesh42,
                       in_specs=(P(), P("data", None)), out_specs=P("data", None),
                       check_vma=False)
    w = jax.ShapeDtypeStruct((L, 32, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    stats = analyze_module(jax.jit(sm).lower(w, x).compile().as_text())
    ars = [c for c in stats.collectives if c.kind == "all-reduce"]
    total_count = sum(c.multiplicity for c in ars)
    assert total_count == pytest.approx(L)


def test_conv_flops_counted():
    x = jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 3, 8), jnp.float32)

    def fn(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    stats = analyze_module(jax.jit(fn).lower(x, w).compile().as_text())
    expect = 2 * (2 * 16 * 16 * 8) * (3 * 3 * 3)
    assert stats.conv_flops == pytest.approx(expect, rel=0.35)
