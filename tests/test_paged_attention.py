"""Paged-attention kernel family (repro.kernels.paged_attention).

The Pallas kernel (interpret mode) must match the pure-jnp oracle, the
oracle must match the slotted ring-cache decode path on identical K/V
(the invariant behind paged == slotted engine equivalence), and the
reserved trash page must be unreadable through any valid (table, length)
pair.  No hypothesis dependency — these always run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_mla_attention,
                                               paged_mla_prefill,
                                               paged_prefill,
                                               paged_ring_prefill)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_mla_attention_ref,
                                               paged_mla_prefill_ref,
                                               paged_prefill_ref,
                                               paged_ring_prefill_ref,
                                               ring_positions)


def _paged_case(rng, slots, H, KV, hd, ps, n, dtype, lengths=None):
    """Random pool + disjoint per-slot page tables + lengths (random in
    [1, n * ps] unless given; a length past the table's cells, which only
    the ring takes, holds every page)."""
    P = slots * n + 1                               # page 0 = trash
    q = jnp.asarray(rng.normal(size=(slots, H, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, KV, ps, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, KV, ps, hd)), dtype)
    if lengths is None:
        lengths = rng.integers(1, n * ps + 1, size=slots)
    lengths = np.asarray(lengths, np.int32)
    table = np.zeros((slots, n), np.int32)
    pid = 1
    for s in range(slots):
        for i in range(-(-min(int(lengths[s]), n * ps) // ps)):
            table[s, i] = pid
            pid += 1
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths)


# A table of 21 pages of 16 tokens: the kernel walks 16 such pages (256
# tokens) per block at these widths, so it takes two blocks, the second a
# tail of 5 pages
MULTI_BLOCK = (3, 4, 2, 32, 16, 21, jnp.float32)

PAGED_CASES = [
    # (slots, H, KV, hd, ps, n_table, dtype, lengths — None: random)
    (3, 4, 2, 32, 8, 4, jnp.float32, None),
    (2, 8, 8, 16, 4, 3, jnp.float32, None),     # MHA (G = 1)
    (1, 2, 1, 64, 16, 2, jnp.float32, None),    # MQA
    (4, 4, 2, 32, 8, 1, jnp.float32, None),     # single-page table
    (2, 4, 2, 32, 8, 3, jnp.bfloat16, None),    # bf16 i/o
    (*MULTI_BLOCK, None),                       # two blocks, a tail block
    (4, *MULTI_BLOCK[1:], (256, 1, 336, 257)),  # block edges, 1 token, full
    (8, *MULTI_BLOCK[1:], (1, 1, 1, 300, 1, 1, 1, 1)),  # mostly one token
]


def _case_id(case):
    """The case's values joined by '-'; given lengths add a last part."""
    *shape, dtype, lengths = case
    parts = [*map(str, shape), dtype.__name__]
    if lengths is not None:
        parts.append("len" + "_".join(map(str, lengths)))
    return "-".join(parts)


def _cases(cases):
    return [pytest.param(*c, id=_case_id(c)) for c in cases]


@pytest.mark.parametrize("slots,H,KV,hd,ps,n,dtype,lengths",
                         _cases(PAGED_CASES))
def test_paged_kernel_matches_ref(rng, slots, H, KV, hd, ps, n, dtype,
                                  lengths):
    """Both page geometries: the contiguous table, and the same table as a
    ring of ``n * ps`` cells that slots half a ring longer have wrapped."""
    q, kp, vp, table, lengths = _paged_case(rng, slots, H, KV, hd, ps, n,
                                            dtype, lengths)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for window, ln in ((0, lengths), (n * ps, lengths + n * ps // 2)):
        ref = paged_attention_ref(q, kp, vp, table, ln, window=window)
        out = paged_attention(q, kp, vp, table, ln, window=window,
                              use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=atol)


def test_paged_ref_matches_ring_cache_decode(rng):
    """Pages cut from a contiguous ring cache score identically to
    ``decode_attend`` over that cache — the slotted/paged bridge."""
    from repro.models.attention import decode_attend
    H, KV, hd, ps, n = 4, 2, 32, 8, 3
    Lc = n * ps
    m = 13                                           # valid tokens
    k = jnp.asarray(rng.normal(size=(1, Lc, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Lc, KV, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, H, hd)), jnp.float32)
    pos = jnp.where(jnp.arange(Lc) < m, jnp.arange(Lc), -1).astype(jnp.int32)
    cache = {"k": k, "v": v, "pos": pos, "index": jnp.asarray(m, jnp.int32)}
    ring = decode_attend(q, cache)                   # [1, 1, H, hd]

    kp = jnp.concatenate([jnp.zeros((1, KV, ps, hd)),
                          k[0].reshape(n, ps, KV, hd)
                          .transpose(0, 2, 1, 3)])   # page 0 = trash
    vp = jnp.concatenate([jnp.zeros((1, KV, ps, hd)),
                          v[0].reshape(n, ps, KV, hd)
                          .transpose(0, 2, 1, 3)])
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    paged = paged_attention(q[:, 0], kp, vp, table,
                            jnp.asarray([m], jnp.int32))
    np.testing.assert_allclose(np.asarray(paged), np.asarray(ring[:, 0]),
                               atol=1e-5)


@pytest.mark.parametrize("m", [5, 8, 13, 24, 29])
def test_paged_window_ref_matches_ring_cache_decode(rng, m):
    """Ring-wrapped window pages score identically to ``decode_attend``
    with a window over the equivalent slotted ring cache — the windowed
    slotted/paged bridge, across pre-wrap, exact-wrap and wrapped fills."""
    from repro.models.attention import decode_attend
    H, KV, hd, ps, n = 4, 2, 32, 8, 3
    window = n * ps                                  # 24 = ring capacity
    Lc = window
    q = jnp.asarray(rng.normal(size=(1, 1, H, hd)), jnp.float32)
    # write positions 0..m-1 into the slotted ring (slot = pos % Lc)
    k = jnp.zeros((1, Lc, KV, hd))
    v = jnp.zeros((1, Lc, KV, hd))
    pos = np.full((Lc,), -1, np.int32)
    for p_ in range(m):
        k = k.at[:, p_ % Lc].set(rng.normal(size=(KV, hd)))
        v = v.at[:, p_ % Lc].set(rng.normal(size=(KV, hd)))
        pos[p_ % Lc] = p_
    cache = {"k": k, "v": v, "pos": jnp.asarray(pos),
             "index": jnp.asarray(m, jnp.int32)}
    ring = decode_attend(q, cache, window=window)

    kp = jnp.concatenate([jnp.zeros((1, KV, ps, hd)),
                          k[0].reshape(n, ps, KV, hd)
                          .transpose(0, 2, 1, 3)])
    vp = jnp.concatenate([jnp.zeros((1, KV, ps, hd)),
                          v[0].reshape(n, ps, KV, hd)
                          .transpose(0, 2, 1, 3)])
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    lengths = jnp.asarray([m], jnp.int32)
    for use_kernel in (False, True):
        paged = paged_attention(q[:, 0], kp, vp, table, lengths,
                                window=window, use_kernel=use_kernel,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(ring[:, 0]),
                                   atol=2e-5)


def _quantized_pages(kp, vp):
    """Quantize a random head-major fp pool [P, KV, ps, hd] the way the
    pool's write paths do; scales come back as [P, KV, 1, ps] pages."""
    from repro.serving.layouts import quantize_kv
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    return kq, vq, ks[:, :, None, :], vs[:, :, None, :]


@pytest.mark.parametrize("window", [0, 24], ids=["full", "ring"])
@pytest.mark.parametrize("slots,H,KV,hd,ps,n,dtype,lengths",
                         _cases(PAGED_CASES[:2] + [(*MULTI_BLOCK, None)]))
def test_paged_kernel_matches_ref_quantized(rng, slots, H, KV, hd, ps, n,
                                            dtype, lengths, window):
    """Int8 pages + per-row scales: the fused-dequant kernel must match the
    fused-dequant oracle on both page geometries (full and ring)."""
    if window and window != n * ps:
        window = n * ps
    q, kp, vp, table, lengths = _paged_case(rng, slots, H, KV, hd, ps, n,
                                            dtype, lengths)
    kq, vq, ks, vs = _quantized_pages(kp, vp)
    ref = paged_attention_ref(q, kq, vq, table, lengths, window=window,
                              k_scale=ks, v_scale=vs)
    out = paged_attention(q, kq, vq, table, lengths, window=window,
                          k_scale=ks, v_scale=vs, use_kernel=True,
                          interpret=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_paged_kernel_quantized_tracks_fp(rng):
    """The fused int8 path is an *approximation* of fp attention — its
    output must track the fp oracle on the same pre-quantization pool
    within int8 resolution (loose tolerance, the accuracy argument)."""
    q, kp, vp, table, lengths = _paged_case(rng, 3, 4, 2, 32, 8, 4,
                                            jnp.float32)
    kq, vq, ks, vs = _quantized_pages(kp, vp)
    fp = paged_attention_ref(q, kp, vp, table, lengths)
    qd = paged_attention_ref(q, kq, vq, table, lengths, k_scale=ks,
                             v_scale=vs)
    np.testing.assert_allclose(np.asarray(qd), np.asarray(fp),
                               atol=0.05, rtol=0.05)


def test_ring_positions_formula():
    """Each ring index resolves to the latest written position congruent to
    it; never-written cells come back invalid."""
    p, valid = ring_positions(jnp.asarray([5, 8, 13], jnp.int32), 8, 8)
    p, valid = np.asarray(p), np.asarray(valid)
    np.testing.assert_array_equal(p[0][:5], np.arange(5))    # pre-wrap
    assert not valid[0][5:].any()
    np.testing.assert_array_equal(p[1], np.arange(8))        # exact fill
    np.testing.assert_array_equal(p[2], [8, 9, 10, 11, 12, 5, 6, 7])


def test_paged_window_kernel_matches_ref(rng):
    slots, H, KV, hd, ps, n = 3, 4, 2, 32, 8, 3
    window = n * ps
    q, kp, vp, table, lengths = _paged_case(rng, slots, H, KV, hd, ps, n,
                                            jnp.float32)
    ref = paged_attention_ref(q, kp, vp, table, lengths, window=window)
    out = paged_attention(q, kp, vp, table, lengths, window=window,
                          use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("slots,H,R,rp,ps,n", [
    (3, 4, 32, 8, 8, 4),
    (2, 8, 16, 16, 4, 3),
    (1, 2, 64, 8, 16, 2),
])
def test_paged_mla_kernel_matches_ref(rng, slots, H, R, rp, ps, n):
    """Latent-page (absorbed MLA) decode kernel vs the jnp oracle."""
    P = slots * n + 1
    q_lat = jnp.asarray(rng.normal(size=(slots, H, R)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(slots, H, rp)), jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(P, ps, R)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(P, ps, rp)), jnp.float32)
    lengths = np.asarray(rng.integers(1, n * ps + 1, size=slots), np.int32)
    table = np.zeros((slots, n), np.int32)
    pid = 1
    for s in range(slots):
        for i in range(-(-int(lengths[s]) // ps)):
            table[s, i] = pid
            pid += 1
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    scale = (R + rp) ** -0.5
    ref = paged_mla_attention_ref(q_lat, q_rope, ckv, kr, table, lengths,
                                  scale=scale)
    out = paged_mla_attention(q_lat, q_rope, ckv, kr, table, lengths,
                              scale=scale, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_mla_trash_page_never_read(rng):
    """Garbage in latent page 0 must not leak through any valid table."""
    slots, H, R, rp, ps, n = 2, 4, 32, 8, 8, 3
    P = slots * n + 1
    q_lat = jnp.asarray(rng.normal(size=(slots, H, R)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(slots, H, rp)), jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(P, ps, R)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(P, ps, rp)), jnp.float32)
    lengths = jnp.asarray([5, 17], jnp.int32)
    table = jnp.asarray([[1, 0, 0], [2, 3, 4]], jnp.int32)
    base = paged_mla_attention(q_lat, q_rope, ckv, kr, table, lengths,
                               scale=0.1)
    ckv2 = ckv.at[0].set(1e4)
    kr2 = kr.at[0].set(-1e4)
    for use_kernel in (False, True):
        out = paged_mla_attention(q_lat, q_rope, ckv2, kr2, table, lengths,
                                  scale=0.1, use_kernel=use_kernel,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=2e-5)


def test_trash_page_never_read(rng):
    """Garbage in page 0 (the write sink for empty slots and padding) must
    not leak into any slot's output."""
    q, kp, vp, table, lengths = _paged_case(rng, 3, 4, 2, 32, 8, 4,
                                            jnp.float32)
    base = paged_attention(q, kp, vp, table, lengths)
    kp2 = kp.at[0].set(1e4)
    vp2 = vp.at[0].set(-1e4)
    for use_kernel in (False, True):
        out = paged_attention(q, kp2, vp2, table, lengths,
                              use_kernel=use_kernel, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("window", [0, 21 * 16], ids=["full", "ring"])
def test_walk_stops_at_length(rng, window, quantized):
    """The kernel reads no table entry past a slot's held pages: pointing
    every slot's table tail at real pages full of NaN changes nothing."""
    slots, H, KV, hd, ps, n, dtype = MULTI_BLOCK
    q, kp, vp, table, lengths = _paged_case(rng, slots, H, KV, hd, ps, n,
                                            dtype, (1, 256, 260))
    held = -(-np.asarray(lengths) // ps)
    P = kp.shape[0]
    tails = np.asarray(table).copy()
    for s in range(slots):
        tails[s, held[s]:] = P + np.arange(n - held[s])
    scales, nan_scales = {}, {}
    if quantized:                  # int8 cannot hold NaN: its scales do
        kp, vp, ks, vs = _quantized_pages(kp, vp)
        scales = dict(k_scale=ks, v_scale=vs)
        nan = jnp.full((n,) + ks.shape[1:], jnp.nan, ks.dtype)
        nan_scales = {k: jnp.concatenate([x, nan]) for k, x in scales.items()}
        tail = jnp.full((n,) + kp.shape[1:], 127, kp.dtype)
    else:
        tail = jnp.full((n,) + kp.shape[1:], jnp.nan, dtype)
    kn, vn = jnp.concatenate([kp, tail]), jnp.concatenate([vp, tail])
    ref = paged_attention_ref(q, kp, vp, table, lengths, window=window,
                              **scales)
    out = paged_attention(q, kn, vn, jnp.asarray(tails), lengths,
                          window=window, use_kernel=True, interpret=True,
                          **nan_scales)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

# ---------------------------------------------------------------------------
# Chunked prefill (one request's bucketed chunk; rows >= n_valid are bucket
# padding — undefined by contract, so every comparison slices [:n_valid])
# ---------------------------------------------------------------------------

def _prefill_case(rng, H, KV, hd, ps, n, S, dtype=jnp.float32):
    """Random page pool + the request's table row [n] (pages 1..n)."""
    q = jnp.asarray(rng.normal(size=(S, H, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(n + 1, KV, ps, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(n + 1, KV, ps, hd)), dtype)
    table = jnp.arange(1, n + 1, dtype=jnp.int32)
    return q, kp, vp, table


PREFILL_CASES = [
    # (H, KV, hd, ps, n, S, start, n_valid, dtype)
    (4, 2, 32, 8, 4, 16, 0, 16, jnp.float32),    # cold, full bucket
    (4, 2, 32, 8, 4, 16, 10, 13, jnp.float32),   # deep start + padded tail
    (8, 8, 16, 4, 8, 8, 3, 5, jnp.float32),      # MHA (G = 1)
    (2, 1, 64, 16, 2, 16, 0, 9, jnp.float32),    # MQA, padded tail
    (4, 2, 32, 8, 4, 8, 17, 8, jnp.bfloat16),    # bf16 i/o, deep start
    # multi-q-block bucket: S=256 -> q_block=128; n_valid=100 leaves the
    # second block fully padded (grid-level skip: bucket-tail waste fix)
    (4, 2, 32, 16, 7, 256, 0, 100, jnp.float32),
]


@pytest.mark.parametrize("H,KV,hd,ps,n,S,start,n_valid,dtype",
                         PREFILL_CASES)
def test_paged_prefill_kernel_matches_ref(rng, H, KV, hd, ps, n, S, start,
                                          n_valid, dtype):
    q, kp, vp, table = _prefill_case(rng, H, KV, hd, ps, n, S, dtype)
    assert start + n_valid <= n * ps
    ref = paged_prefill_ref(q, kp, vp, table, start, n_valid)
    out = paged_prefill(q, kp, vp, table, start, n_valid, use_kernel=True,
                        interpret=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out[:n_valid], np.float32),
                               np.asarray(ref[:n_valid], np.float32),
                               atol=atol)


def test_paged_prefill_padded_qblocks_emit_zero(rng):
    """Fully padded q blocks are skipped at grid level and emit exact
    zeros — the bucket tail costs no MXU cycles (and no garbage)."""
    H, KV, hd, ps, n, S, n_valid = 4, 2, 32, 16, 7, 256, 100
    q, kp, vp, table = _prefill_case(rng, H, KV, hd, ps, n, S)
    out = paged_prefill(q, kp, vp, table, 0, n_valid, use_kernel=True,
                        interpret=True)
    # q_block = 128: rows 128..255 form an entirely-padded block
    np.testing.assert_array_equal(np.asarray(out[128:]), 0.0)


def test_paged_prefill_trash_page_never_read(rng):
    """Table tail entries point at page 0; garbage there must not leak
    into any valid row (kernel skips those pages entirely)."""
    H, KV, hd, ps, n, S = 4, 2, 32, 8, 4, 16
    start, n_valid = 3, 10                        # occupies pages 1..2
    q, kp, vp, _ = _prefill_case(rng, H, KV, hd, ps, n, S)
    table = jnp.asarray([1, 2, 0, 0], jnp.int32)  # tail = trash
    base = paged_prefill(q, kp, vp, table, start, n_valid)
    kp2 = kp.at[0].set(1e4)
    vp2 = vp.at[0].set(-1e4)
    for use_kernel in (False, True):
        out = paged_prefill(q, kp2, vp2, table, start, n_valid,
                            use_kernel=use_kernel, interpret=True)
        np.testing.assert_allclose(np.asarray(out[:n_valid]),
                                   np.asarray(base[:n_valid]), atol=2e-5)


@pytest.mark.parametrize("H,KV,hd,ps,n,S,start,n_valid,dtype",
                         PREFILL_CASES[:2] + PREFILL_CASES[-1:])
def test_paged_prefill_kernel_matches_ref_quantized(rng, H, KV, hd, ps, n,
                                                    S, start, n_valid,
                                                    dtype):
    """Chunked prefill against int8 pages: fused-dequant kernel vs the
    fused-dequant oracle (incl. the multi-q-block padded-tail case)."""
    q, kp, vp, table = _prefill_case(rng, H, KV, hd, ps, n, S, dtype)
    kq, vq, ks, vs = _quantized_pages(kp, vp)
    ref = paged_prefill_ref(q, kq, vq, table, start, n_valid, k_scale=ks,
                            v_scale=vs)
    out = paged_prefill(q, kq, vq, table, start, n_valid, k_scale=ks,
                        v_scale=vs, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:n_valid], np.float32),
                               np.asarray(ref[:n_valid], np.float32),
                               atol=2e-5)


RING_PREFILL_CASES = [
    # (H, KV, hd, ps, n, S, start, n_valid) — window = n * ps
    (4, 2, 32, 8, 3, 16, 0, 16),     # cold start (ring empty)
    (4, 2, 32, 8, 3, 16, 30, 13),    # ring fully wrapped before the chunk
    (8, 8, 16, 4, 4, 8, 10, 5),      # MHA, partially filled ring
    (2, 1, 64, 8, 2, 32, 70, 27),    # chunk wider than the window (S > w)
]


@pytest.mark.parametrize("H,KV,hd,ps,n,S,start,n_valid",
                         RING_PREFILL_CASES)
def test_paged_ring_prefill_kernel_matches_ref(rng, H, KV, hd, ps, n, S,
                                               start, n_valid):
    window = n * ps
    q, kp, vp, table = _prefill_case(rng, H, KV, hd, ps, n, S)
    ck = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    ref = paged_ring_prefill_ref(q, kp, vp, ck, cv, table, start, n_valid,
                                 window=window)
    out = paged_ring_prefill(q, kp, vp, ck, cv, table, start, n_valid,
                             window=window, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:n_valid]),
                               np.asarray(ref[:n_valid]), atol=2e-5)


@pytest.mark.parametrize("H,KV,hd,ps,n,S,start,n_valid",
                         RING_PREFILL_CASES)
def test_paged_ring_prefill_kernel_matches_ref_quantized(rng, H, KV, hd,
                                                         ps, n, S, start,
                                                         n_valid):
    """Ring chunked prefill with an int8 *snapshot*: the ring pages carry
    scales, the chunk's ride-along K/V stay fp (scale 1) — kernel vs
    oracle across cold, wrapped and wider-than-window chunks."""
    window = n * ps
    q, kp, vp, table = _prefill_case(rng, H, KV, hd, ps, n, S)
    ck = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    kq, vq, ks, vs = _quantized_pages(kp, vp)
    ref = paged_ring_prefill_ref(q, kq, vq, ck, cv, table, start, n_valid,
                                 window=window, k_scale=ks, v_scale=vs)
    out = paged_ring_prefill(q, kq, vq, ck, cv, table, start, n_valid,
                             window=window, k_scale=ks, v_scale=vs,
                             use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:n_valid]),
                               np.asarray(ref[:n_valid]), atol=2e-5)


def test_paged_ring_prefill_snapshot_semantics(rng):
    """The kernel must read the chunk's own K/V from the ride-along
    operands, never back through the (post-write) ring pages: poisoning
    the pages at the chunk's own write cells must not change output when
    the snapshot is passed."""
    H, KV, hd, ps, n = 4, 2, 32, 8, 3
    window, S, start, n_valid = 24, 16, 30, 13
    q, kp, vp, table = _prefill_case(rng, H, KV, hd, ps, n, S)
    ck = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(S, KV, hd)), jnp.float32)
    base = [paged_ring_prefill(q, kp, vp, ck, cv, table, start, n_valid,
                               window=window, use_kernel=uk, interpret=True)
            for uk in (False, True)]
    np.testing.assert_allclose(np.asarray(base[1][:n_valid]),
                               np.asarray(base[0][:n_valid]), atol=2e-5)


MLA_PREFILL_CASES = [
    # (H, R, rp, ps, n, S, start, n_valid)
    (4, 32, 8, 8, 4, 16, 0, 16),
    (2, 16, 16, 4, 8, 8, 5, 6),
    (4, 32, 8, 16, 7, 256, 0, 100),   # multi-q-block + padded tail block
]


@pytest.mark.parametrize("H,R,rp,ps,n,S,start,n_valid", MLA_PREFILL_CASES)
def test_paged_mla_prefill_kernel_matches_ref(rng, H, R, rp, ps, n, S,
                                              start, n_valid):
    q_lat = jnp.asarray(rng.normal(size=(S, H, R)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(S, H, rp)), jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(n + 1, ps, R)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(n + 1, ps, rp)), jnp.float32)
    table = jnp.arange(1, n + 1, dtype=jnp.int32)
    scale = (R + rp) ** -0.5
    ref = paged_mla_prefill_ref(q_lat, q_rope, ckv, kr, table, start,
                                n_valid, scale=scale)
    out = paged_mla_prefill(q_lat, q_rope, ckv, kr, table, start, n_valid,
                            scale=scale, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:n_valid]),
                               np.asarray(ref[:n_valid]), atol=2e-5)
