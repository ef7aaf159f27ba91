"""Pluggable physical KV-page layouts — the runtime seam behind paged serving.

The paper's transparency principle says distribution (and serving) machinery
lives in the runtime, never in user code.  Until this module existed that
leaked: ``attn_kind == "full"`` probes scattered across the registry, the
page pool and the engine silently dropped MLA and sliding-window families
onto the slotted fallback, losing paged oversubscription and the prefix
cache.  A ``KVLayout`` describes everything the *physical* page format of a
family's decode cache needs:

  * ``leaves``  — which decode-state leaves the page pool tiles into pages
                  (per-head ``("k", "v")`` for GQA; latent ``("ckv",
                  "krope")`` for DeepSeek MLA — the pool itself never names
                  a leaf);
  * ``window``  — 0 for contiguous layouts (token ``t`` lives at page-table
                  column ``t // page_size`` forever); ``> 0`` for
                  *ring-wrapped* window pages: the table is a ring of
                  ``window // page_size`` cells, token ``t`` lives at cell
                  ``(t % window) // page_size``, and a cell's page is reused
                  in place as the sequence wraps — a slot holds at most
                  ``window`` tokens of K/V, matching the slotted ring
                  cache's memory exactly while keeping page-granular lazy
                  growth and prefix sharing;
  * ``kv_dtype`` — the *storage* dtype of the data leaves: ``"fp32"`` keeps
                  the family's native compute dtype; ``"int8"`` stores each
                  k/v row as int8 with a per-(page, kv-head, offset)
                  symmetric bfloat16 scale carried as an extra ``*_scale``
                  leaf
                  (``quantize_kv`` is the single quantizer — write paths
                  call it; the paged-attention kernels and their jnp
                  oracles multiply the scales back into the online-softmax
                  accumulation, never materializing fp pages).

Per-head pages are stored *head-major*: a k/v leaf is [L, P, KV, ps, hd]
and a scale leaf [L, P, KV, 1, ps], so one (page, kv-head) block is a
``(ps, hd)`` tile — the block shape the TPU's Pallas kernels can DMA
(``kernels.paged_attention.kernel``).  The bundle's native decode state is
token-major ([..., T, KV, hd]); ``KVLayout.to_pages``, ``write_rows`` and
``gather_rows`` are the only conversions between the two.  Latent pages
keep the native [L, P, ps, R] layout.

``layout_for(cfg)`` is the single capability authority: the registry asks
it (instead of probing ``attn_kind`` strings) whether a family pages, and
the engine/pool take the returned layout as a constructor argument.  A new
cache format (quantized KV, hybrid local/global) plugs in by adding a
layout here — no pool/engine/registry surgery.  Quantized variants derive
from the base layouts via ``quantized_layout`` (the engine applies
``ServeConfig.kv_dtype`` there); MLA latent pages stay fp because rank is
a *contracted* dim — per-page latent scales would reassociate the absorbed
sums, so ``kv_dtype="int8"`` + ``attn_kind="mla"`` is rejected.

Import discipline: this module depends only on jax — it sits *below* both
``repro.models.registry`` (which imports ``layout_for``) and
``repro.serving.paged`` (which takes a layout), so neither layer reaches
around the seam.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

P = jax.sharding.PartitionSpec

#: storage dtypes a layout's data leaves may use ("fp32" = native compute
#: dtype — the name records what the *bench baselines* store, fp32 on the
#: smoke configs).  ``ServeConfig.kv_dtype`` validates against this.
KV_DTYPES = ("fp32", "int8")

#: suffix of the per-row scale leaf a quantized layout carries beside each
#: data leaf ("k" -> "k_scale")
SCALE_SUFFIX = "_scale"


def quantize_kv(x):
    """Symmetric per-row int8 quantization of a K/V leaf: one bfloat16
    scale per (..., head) row over the trailing head_dim.  Returns
    ``(q, scale)`` with ``q`` int8 in [-127, 127] and
    ``x ≈ q * scale[..., None]``.

    The scale is *stored* bf16 (half the overhead of fp32 — what keeps the
    quantized page under the 0.30x budget on the hd=16 smoke shapes) but
    the row is divided by the bf16-*rounded* value, so dequant with the
    stored scale reconstructs exactly what was quantized; the clip guards
    the ≤0.4% bf16 round-down that could push a ratio past 127.

    This is the ONLY quantizer — the decode append, the prefill scatter and
    the whole-state insert path all call it, so a written token's page
    bytes are a pure function of its fp row (the warm/cold, mesh and
    kernel-on/off identity argument for quantized layouts)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.bfloat16)
    sf = scale.astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / sf[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def check_kv_dtype_layout(kv_dtype: str, layout: Optional["KVLayout"]) -> None:
    """Quantized KV needs a per-head paged layout.  The ONLY implementation
    of this rule — ``quantized_layout`` (engine-side derivation) and
    ``ServeConfig.check_kv_dtype`` (engine-level validation) both call it.

    MLA latent pages stay fp: the latent rank is a *contracted* dim of the
    absorbed-decode einsums, so per-page scales would reassociate those
    sums and break the latent == per-head equivalence."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype={kv_dtype!r} not in {KV_DTYPES}")
    if kv_dtype == "fp32":
        return
    if layout is None:
        raise ValueError(
            f"kv_dtype={kv_dtype!r} requires a paged KV layout, but this "
            "family serves slotted-only (no layout) — drop kv_dtype or "
            "pick a paged family")
    if layout.name == "latent":
        raise ValueError(
            f"kv_dtype={kv_dtype!r} cannot quantize MLA latent pages "
            f"(attn_kind='mla'): the latent rank is a contracted dim, so "
            "per-page scales would reassociate the absorbed sums — use "
            "kv_dtype='fp32' with attn_kind='mla'")


def check_window_page_size(page_size: int, window: int) -> None:
    """Ring-wrapped window pages must *tile* the window: a page larger than
    the window could never fill before rotating out (so it could never be
    cached or freed correctly), and a page that doesn't divide the window
    would straddle the wrap point.  The ONLY implementation of this rule —
    ``KVLayout.check_page_size`` (pool construction) and
    ``ServeConfig.check_window`` (engine-level validation) both call it."""
    if window <= 0:
        return
    if page_size > window:
        raise ValueError(
            f"page_size={page_size} exceeds the attention window="
            f"{window}: a page that never fits the window can never be "
            "cached or freed correctly — shrink page_size or force "
            "kv_layout='slotted'")
    if window % page_size:
        raise ValueError(
            f"page_size={page_size} does not divide the attention "
            f"window={window}: ring-wrapped window pages must tile the "
            "window exactly")


@dataclass(frozen=True)
class KVLayout:
    """Physical page layout of one attention family's decode cache."""
    name: str                    # "kv" | "latent" | "window"
    leaves: Tuple[str, ...]      # decode-state leaves the pool pages
    window: int = 0              # > 0: ring-wrapped window pages
    kv_dtype: str = "fp32"       # "fp32" (native) | "int8" (+ scale leaves)

    # -- geometry ----------------------------------------------------------

    @property
    def ring(self) -> bool:
        return self.window > 0

    @property
    def quantized(self) -> bool:
        return self.kv_dtype != "fp32"

    @property
    def data_leaves(self) -> Tuple[str, ...]:
        """The K/V-carrying leaves — ``leaves`` minus the scale leaves a
        quantized layout appends.  These are the names present in the
        bundle's native decode state (``init_decode_state`` / slotted
        prefill caches); scale leaves exist only in the page pool."""
        return tuple(n for n in self.leaves if not n.endswith(SCALE_SUFFIX))

    def to_pages(self, rows: dict) -> dict:
        """Page storage of token-major data leaves [..., ps, ...] (whole
        pages of the bundle's native state).  Per-head leaves
        [..., ps, KV, hd] become head-major [..., KV, ps, hd]; quantized
        layouts store them int8 beside a [..., KV, 1, ps] bf16 scale leaf
        (``quantize_kv``).  Latent leaves store as given."""
        if self.name == "latent":
            return dict(rows)
        out = {}
        for name, x in rows.items():
            if self.quantized:
                x, scale = quantize_kv(x)
                out[name + SCALE_SUFFIX] = jnp.swapaxes(scale, -2, -1)[
                    ..., None, :]
            out[name] = jnp.swapaxes(x, -3, -2)
        return out

    def page_template(self, blank: dict) -> dict:
        """One-page pool template from the bundle's native blank state
        ([L, 1, ps, ...] data leaves) in page storage (``to_pages``)."""
        return self.to_pages({k: blank[k] for k in self.data_leaves})

    def check_page_size(self, page_size: int) -> None:
        """Ring layouts need pages that tile the window (see
        ``check_window_page_size`` — the single home of that rule, also
        reached through ``ServeConfig.check_window``)."""
        check_window_page_size(page_size, self.window)

    def max_page_size(self) -> int:
        """Largest power-of-two page that satisfies ``check_page_size``:
        the lowest set bit of the window (every smaller power of two also
        tiles it).  Unbounded (2**62) for contiguous layouts — callers
        min() it against their own caps."""
        return self.window & -self.window if self.window else 1 << 62

    def table_width(self, pages_per_slot: int, page_size: int) -> int:
        """Page-table columns per slot: the full logical block count for
        contiguous layouts, the ring size for windowed ones."""
        if not self.window:
            return pages_per_slot
        return min(pages_per_slot, self.window // page_size)

    def cell(self, block: int, width: int) -> int:
        """Table column holding logical block ``block``."""
        return block % width if self.ring else block

    def live_tokens(self, seq_len: int) -> int:
        """Tokens of K/V a slot holds at sequence length ``seq_len`` — what
        a slot-granular pool would preallocate (the telemetry comparator)."""
        return min(seq_len, self.window) if self.ring else seq_len

    def max_chunk_tokens(self, padded_len: int) -> int:
        """Largest prefill chunk the layout can absorb in one write-then-
        attend step.  A ring chunk longer than the window would overwrite
        cells its own early queries (and the snapshot gather) still need."""
        return self.window if self.ring else padded_len

    def max_decode_span(self, n_steps: int) -> int:
        """Longest decode span one fused multi-step dispatch may write.
        The pipelined engine prepares a slot's whole span (positions
        ``pos..pos+span-1``) before dispatching its ``lax.scan`` decode;
        a ring span longer than the window would wrap onto cells whose
        keys its own earlier scan iterations still attend — same hazard,
        same bound as ``max_chunk_tokens``.  Contiguous layouts are
        unconstrained."""
        return min(n_steps, self.window) if self.ring else n_steps

    def needed_start(self, cached_tokens: int, page_size: int) -> int:
        """First prompt block a new admission must still be able to *read*
        when ``cached_tokens`` are served from the prefix cache: suffix
        queries start at position ``cached_tokens`` and attend keys no
        older than ``cached_tokens - window + 1`` — earlier blocks are
        wholly masked and need no live page (contiguous layouts need every
        block)."""
        if not self.window:
            return 0
        return max(0, cached_tokens - self.window + 1) // page_size

    # -- observability -----------------------------------------------------

    def describe(self) -> dict:
        """Flat JSON-friendly identity for trace metadata: which physical
        page format a trace was captured against (``repro.obs`` stamps it
        into the Chrome trace's ``otherData`` and the pool's init event),
        so an attribution number is never read against the wrong layout."""
        return {"layout": self.name, "leaves": list(self.leaves),
                "window": self.window, "ring": self.ring,
                "kv_dtype": self.kv_dtype}

    # -- sharding ----------------------------------------------------------

    def page_pspec(self, name: str, leaf, model_size: int):
        """PartitionSpec for one page-pool leaf.  KV-head (or head_dim) of
        per-head pages shards over "model" when divisible (a *batch* dim of
        the attention einsums — sharding it never reassociates a sum).
        Latent (ckv/krope) pages replicate: the rank is a *contracted* dim
        in every absorbed-MLA einsum, so sharding it would split dot
        products across devices and break bitwise equivalence with the
        single-device decode — and the latent cache is small by
        construction (that is MLA's point), so replication is cheap.
        Pages themselves always replicate over data axes — any slot's
        pages live anywhere."""
        spec = [None] * leaf.ndim
        if model_size > 1 and name in ("k", "v") and leaf.ndim == 5:
            if leaf.shape[2] % model_size == 0:           # [L,P,KV,ps,hd]
                spec[2] = "model"
            elif leaf.shape[4] % model_size == 0:
                spec[4] = "model"
        if model_size > 1 and name in ("k_scale", "v_scale") \
                and leaf.ndim == 5:
            # [L,P,KV,1,ps] — shard KV exactly when the int8 data leaf does
            # (same divisibility test on the same axis); when the data leaf
            # fell back to head_dim sharding the scales replicate, which is
            # consistent because their KV dim is then unsharded too.
            if leaf.shape[2] % model_size == 0:
                spec[2] = "model"
        return P(*spec)


def write_rows(kv, name: str, page, off, rows):
    """Scatter token rows [N, KV, hd] of per-head leaf ``name`` into one
    layer's head-major pages at (``page``, ``off``) [N].  A pool that
    carries the leaf's scale quantizes the rows (``quantize_kv``) and
    scatters int8 rows and scales to the same cells.  Returns the updated
    leaves."""
    sname = name + SCALE_SUFFIX
    if sname not in kv:
        return {name: kv[name].at[page, :, off].set(
            rows.astype(kv[name].dtype))}
    q, scale = quantize_kv(rows)
    return {name: kv[name].at[page, :, off].set(q),
            sname: kv[sname].at[page, :, 0, off].set(scale)}


def gather_rows(pages, ids):
    """Token-major rows [n*ps, KV, hd] of one layer's head-major per-head
    pages [P, KV, ps, hd] at page ids [n]."""
    x = jnp.swapaxes(pages[ids], 1, 2)                    # [n, ps, KV, hd]
    return x.reshape(-1, *x.shape[2:])


#: the three shipped layouts (module-level so capability checks and tests
#: can name them without constructing)
KV_FULL = KVLayout("kv", ("k", "v"))
KV_LATENT = KVLayout("latent", ("ckv", "krope"))


def quantized_layout(base: Optional[KVLayout],
                     kv_dtype: str) -> Optional[KVLayout]:
    """Derive the ``kv_dtype`` storage variant of a base layout: identity
    for "fp32"; "int8" appends one ``*_scale`` leaf per data leaf and marks
    the layout quantized.  The engine applies ``ServeConfig.kv_dtype`` here
    (right beside its ``check_window`` call); raises the same ValueError as
    ``check_kv_dtype_layout`` for un-quantizable layouts (MLA latent /
    slotted-only)."""
    if kv_dtype == "fp32":
        return base
    check_kv_dtype_layout(kv_dtype, base)
    leaves = base.leaves + tuple(n + SCALE_SUFFIX for n in base.leaves)
    return KVLayout(base.name, leaves, window=base.window, kv_dtype=kv_dtype)


def layout_for(cfg, kv_dtype: str = "fp32") -> Optional[KVLayout]:
    """The capability authority: which page layout (if any) serves this
    model config's decode cache.  Returns None for families whose state has
    nothing to page (recurrent O(1) state) — they stay on the slotted pool.

    Callers pass a transformer-family ``ModelConfig``; the registry only
    consults this for families whose decode cache *is* the transformer
    cache (dense / moe), so recurrent hybrids with attention sub-blocks
    never reach here.  ``kv_dtype`` (the ``ServeConfig`` knob) selects the
    storage variant: "int8" emits layouts whose data leaves are int8 pages
    with per-row scale leaves — rejected for MLA with an error naming both
    knobs (latent rank is contracted; see ``check_kv_dtype_layout``).
    """
    kind = getattr(cfg, "attn_kind", "none")
    if kind == "full":
        base = KV_FULL
    elif kind == "mla":
        base = KV_LATENT
    elif kind in ("swa", "local") and getattr(cfg, "window", 0) > 0:
        base = KVLayout("window", ("k", "v"), window=cfg.window)
    else:
        return None
    return quantized_layout(base, kv_dtype)
