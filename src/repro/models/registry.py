"""Architecture registry: one uniform interface over all model families.

A ``ModelBundle`` exposes, per architecture:
  * ``specs``                 — ParamSpec tree (init / dry-run / shardings)
  * ``loss_fn(params,batch)``       — train_* cells
  * ``prefill_fn(params,**inputs)`` — prefill_* cells
  * ``decode_fn(params,tokens,state)`` — decode_* / long_* cells
  * ``*_input_specs(shape)``  — ShapeDtypeStruct stand-ins per assignment
    (modality frontends are stubs: whisper gets frame embeddings, pixtral
    gets patch embeddings)

Decode-path contracts are *typed*: ``TrainStepContract``, ``ServeContract``
and ``PagedServeContract`` below are the Protocols a family implements, and
``ModelBundle.capabilities()`` reports which of them it declares.  Runtime
consumers (``repro.serving``, ``repro.api``) dispatch on the declared
capability set — never on ``is None`` probes against individual fields — so
an unsupported workload fails with one clear error at session-load time.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import (Any, Callable, Dict, FrozenSet, Optional, Protocol,
                    Tuple, runtime_checkable)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import common, transformer, rglru, rwkv6, whisper, pixtral
from repro.serving.layouts import KVLayout, layout_for


S_ = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# Decode-path contracts (typed Protocols; see ModelBundle.capabilities)
# ---------------------------------------------------------------------------

@runtime_checkable
class TrainStepContract(Protocol):
    """Sequential training semantics: ``loss_fn(params, batch) -> scalar``.

    ``batch`` matches ``train_input_specs``; the runtime injects broadcast
    and all-reduce around it (``TransparentTrainer``)."""

    def __call__(self, params, batch) -> jax.Array: ...


@runtime_checkable
class ServeContract(Protocol):
    """Engine-facing prefill: ``(params, tokens, *, cache_len) -> (last_logits,
    state)``.

    ``state`` must match ``init_decode_state(batch, cache_len)`` leaf-for-
    leaf so the engine can insert it into its slot pool without reshaping.
    Paired with ``decode_fn`` for the slotted decode path.

    Families that additionally declare ``"bucketed_prefill"`` accept a
    traced ``n_valid`` keyword: ``tokens`` is then a padded power-of-two
    bucket whose tail past ``n_valid`` is masked out of the cache and the
    logits — the engine's compile-count bound under ragged prompt
    lengths."""

    def __call__(self, params, tokens, *, cache_len: int) -> Tuple[Any, Any]: ...


@runtime_checkable
class PagedServeContract(Protocol):
    """Paged batched decode against the shared page pool:
    ``(params, tokens, state, *, use_pallas=False) -> (logits [slots, V],
    pages)`` with ``state = {"pages": {leaf: [L, P, ...]},
    "page_table": [slots, n] int32, "pos": [slots] int32}``.

    The page leaves are the family's ``KVLayout`` (per-head "k"/"v" for
    GQA — contiguous for full attention, ring-wrapped for swa/local —
    latent "ckv"/"krope" for MLA).  The engine builds the pool from
    ``init_decode_state(1, page_size)``.  ``use_pallas`` selects the
    Pallas paged-attention kernels (TPU) vs the traced jnp references
    (CPU)."""

    def __call__(self, params, tokens, state, *,
                 use_pallas: bool = False) -> Tuple[Any, Any]: ...


@runtime_checkable
class PagedPrefillContract(Protocol):
    """Chunked prefill straight into the page pool (the prefix-cache path):
    ``(params, tokens, state, *, use_pallas=False) -> (logits [1, V],
    pages)`` with ``state = {"pages": {"k","v"}: [L, P, KV, ps, hd],
    "page_table": [n] int32, "start": scalar, "n_valid": scalar}``.

    ``tokens`` [1, S] is one request's uncached suffix chunk padded to a
    power-of-two bucket; ``start`` is how many tokens (shared prefix pages +
    earlier chunks) are already cached, ``n_valid`` how many of the chunk's
    tokens are real.  The function writes the chunk's K/V into the pool and
    attends causally over prefix + chunk, so the engine can admit a request
    whose prompt prefix is already cached without re-running its FLOPs.
    Declaring this contract is what flips on the engine's ``prefix_serve``
    capability (see ``ServeConfig.enable_prefix_cache``)."""

    def __call__(self, params, tokens, state, *,
                 use_pallas: bool = False) -> Tuple[Any, Any]: ...


@runtime_checkable
class PagedVerifyContract(Protocol):
    """Speculative-decode verify forward (the spec-decode path):
    ``(params, tokens, state, *, use_pallas=False) -> (logits [S, V],
    pages)`` with the same ``state`` as ``PagedPrefillContract``.

    ``tokens`` [1, S] holds one slot's last committed token followed by
    its draft tokens (fixed width ``spec_tokens + 1``; the tail past
    ``n_valid`` is masked into the trash page).  Unlike the prefill
    contract the head runs over *every* position: row ``j`` predicts
    sequence index ``start + 1 + j``, which is exactly what the engine
    replays its per-request sampler over to find the accepted draft
    prefix.  Declaring this contract flips on the engine's
    ``spec_serve`` capability (see ``ServeConfig.enable_spec``)."""

    def __call__(self, params, tokens, state, *,
                 use_pallas: bool = False) -> Tuple[Any, Any]: ...


#: capability names a bundle may declare (see ModelBundle.capabilities)
CAPABILITIES = ("train", "serve", "paged_serve", "prefix_serve",
                "spec_serve", "bucketed_prefill")


@dataclass
class ModelBundle:
    cfg: ModelConfig
    specs: Any
    loss_fn: TrainStepContract            # (params, batch) -> scalar loss
    prefill_fn: Optional[Callable]        # (params, **inputs) -> (logits, state)
    decode_fn: Optional[Callable]         # (params, tokens, state) -> (logits, state)
    train_input_specs: Callable           # (ShapeConfig) -> dict of SDS
    prefill_input_specs: Callable
    decode_state_specs: Callable          # (ShapeConfig) -> state SDS tree
    init_decode_state: Callable           # (batch, seq_len) -> state arrays
    # Serving decode-path contract (repro.serving): a ``ServeContract``
    # prefill that emits a decode state sized for an engine-owned KV slot of
    # capacity ``cache_len`` (token budget = prompt + generated).  None for
    # families the engine does not serve yet (encdec / vlm frontends need
    # per-request modality inputs).
    serve_prefill_fn: Optional[ServeContract] = None
    # Paged decode contract (``PagedServeContract``).  Present exactly when
    # ``kv_layout`` is — the layout seam (``repro.serving.layouts``) is the
    # single capability authority; recurrent families (RG-LRU conv/hidden
    # and RWKV wkv state are O(1) per slot — nothing to page) have no
    # layout and stay slotted.
    paged_decode_fn: Optional[PagedServeContract] = None
    # Paged prefill contract (``PagedPrefillContract``): chunked prefill
    # into the page pool, the mechanism behind prefix caching and chunked
    # prefill.  Same layout gate as paged_decode_fn.
    paged_prefill_fn: Optional[PagedPrefillContract] = None
    # Speculative-decode verify contract (``PagedVerifyContract``): the
    # all-position-logits sibling of paged_prefill_fn.  Same layout gate.
    paged_verify_fn: Optional[PagedVerifyContract] = None
    # Physical page layout of the decode cache (None = slotted only); the
    # engine hands this to ``PagedKVCachePool`` and validates page-size /
    # window compatibility against it.
    kv_layout: Optional[KVLayout] = None
    # True when serve_prefill_fn accepts a traced ``n_valid`` (masked bucket
    # tail).  Recurrent families advance their state token-by-token, so
    # tail padding would corrupt it; ring-caching families (swa/local) wrap
    # padding onto valid slots in the *slotted* prefill cache — both keep
    # exact-length slotted prefills (the paged chunk path buckets every
    # layout: its tails route to the trash page).
    masked_prefill: bool = False

    def capabilities(self) -> FrozenSet[str]:
        """Declared decode-path contracts (subset of ``CAPABILITIES``).

        ``"train"``            — ``loss_fn`` implements ``TrainStepContract``;
        ``"serve"``            — ``serve_prefill_fn`` (``ServeContract``) +
                                 ``decode_fn`` drive the slotted engine path;
        ``"paged_serve"``      — ``paged_decode_fn`` (``PagedServeContract``)
                                 additionally drives the paged KV pool;
        ``"prefix_serve"``     — ``paged_prefill_fn``
                                 (``PagedPrefillContract``) enables prefix-
                                 cache page sharing + chunked prefill;
        ``"spec_serve"``       — ``paged_verify_fn``
                                 (``PagedVerifyContract``) enables n-gram
                                 speculative decoding;
        ``"bucketed_prefill"`` — serve_prefill_fn takes ``n_valid`` (the
                                 engine may pad prompts to power-of-two
                                 buckets with masked tails).
        """
        caps = set()
        if self.loss_fn is not None:
            caps.add("train")
        if self.serve_prefill_fn is not None and self.decode_fn is not None:
            caps.add("serve")
            if self.masked_prefill:
                caps.add("bucketed_prefill")
        if self.paged_decode_fn is not None:
            caps.add("paged_serve")
            if self.paged_prefill_fn is not None:
                caps.add("prefix_serve")
            if self.paged_verify_fn is not None:
                caps.add("spec_serve")
        return frozenset(caps)

    def param_structs(self):
        return common.param_shape_structs(self.specs)

    def init_params(self, key):
        return common.init_params(self.specs, key)


def _lm_train_specs(cfg, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    return {"tokens": S_((B, S), jnp.int32), "labels": S_((B, S), jnp.int32)}


def _lm_prefill_specs(cfg, shape: ShapeConfig):
    return {"tokens": S_((shape.global_batch, shape.seq_len), jnp.int32)}


def _lm_decode_tokens(shape: ShapeConfig):
    return {"tokens": S_((shape.global_batch, 1), jnp.int32)}


# ---------------------------------------------------------------------------
# Family builders
# ---------------------------------------------------------------------------

def _build_lm(cfg: ModelConfig) -> ModelBundle:
    # the layout seam decides paged capability — never an attn_kind probe
    layout = layout_for(cfg)
    return ModelBundle(
        cfg=cfg,
        specs=transformer.lm_specs(cfg),
        loss_fn=functools.partial(transformer.lm_loss, cfg),
        prefill_fn=lambda params, tokens: transformer.lm_prefill(
            cfg, params, tokens,
            cache_len=transformer.decode_cache_len(cfg, tokens.shape[1])),
        decode_fn=functools.partial(transformer.lm_decode, cfg),
        train_input_specs=functools.partial(_lm_train_specs, cfg),
        prefill_input_specs=functools.partial(_lm_prefill_specs, cfg),
        decode_state_specs=lambda shape: transformer.decode_cache_specs(
            cfg, shape.global_batch, shape.seq_len),
        init_decode_state=functools.partial(
            lambda cfg, b, s: transformer.init_decode_caches(cfg, b, s), cfg),
        # serving prefill routes MoE drop-free per token (moe_dropless):
        # capacity truncation would couple tokens across bucket widths /
        # chunk boundaries / prefix skips and break token identity
        serve_prefill_fn=lambda params, tokens, *, cache_len, n_valid=None:
            transformer.lm_prefill(
                cfg, params, tokens,
                cache_len=transformer.decode_cache_len(cfg, cache_len),
                n_valid=n_valid, moe_dropless=True),
        paged_decode_fn=(functools.partial(transformer.lm_paged_decode, cfg)
                         if layout is not None else None),
        paged_prefill_fn=(functools.partial(transformer.lm_paged_prefill,
                                            cfg)
                          if layout is not None else None),
        paged_verify_fn=(functools.partial(transformer.lm_paged_verify, cfg)
                         if layout is not None else None),
        kv_layout=layout,
        # masked bucket tails need the *slotted* prefill cache to hold the
        # whole bucket (no ring wrap): true for the contiguous layouts,
        # false for ring (window) caches, where padding would wrap onto
        # valid slots — those get bucketing through the paged chunk path
        # instead (tails route to the trash page)
        masked_prefill=(layout is not None and not layout.ring),
    )


def _build_rg(cfg: ModelConfig) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        specs=rglru.rg_specs(cfg),
        loss_fn=functools.partial(rglru.rg_loss, cfg),
        prefill_fn=lambda params, tokens: rglru.rg_prefill(cfg, params, tokens),
        decode_fn=functools.partial(rglru.rg_decode, cfg),
        train_input_specs=functools.partial(_lm_train_specs, cfg),
        prefill_input_specs=functools.partial(_lm_prefill_specs, cfg),
        decode_state_specs=lambda shape: rglru.rg_state_specs(
            cfg, shape.global_batch, shape.seq_len),
        init_decode_state=functools.partial(
            lambda cfg, b, s: rglru.rg_init_states(cfg, b, s), cfg),
        serve_prefill_fn=lambda params, tokens, *, cache_len: rglru.rg_prefill(
            cfg, params, tokens, cache_len=cache_len),
    )


def _build_rwkv(cfg: ModelConfig) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        specs=rwkv6.rwkv_specs(cfg),
        loss_fn=functools.partial(rwkv6.rwkv_loss, cfg),
        prefill_fn=lambda params, tokens: rwkv6.rwkv_prefill(cfg, params, tokens),
        decode_fn=functools.partial(rwkv6.rwkv_decode, cfg),
        train_input_specs=functools.partial(_lm_train_specs, cfg),
        prefill_input_specs=functools.partial(_lm_prefill_specs, cfg),
        decode_state_specs=lambda shape: rwkv6.rwkv_state_specs(
            cfg, shape.global_batch),
        init_decode_state=functools.partial(
            lambda cfg, b, s: rwkv6.rwkv_init_states(cfg, b), cfg),
        # recurrent state is O(1) in sequence length: capacity is a no-op
        serve_prefill_fn=lambda params, tokens, *, cache_len: rwkv6.rwkv_prefill(
            cfg, params, tokens),
    )


def _build_whisper(cfg: ModelConfig) -> ModelBundle:
    e = cfg.encdec

    def train_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        return {
            "frames": S_((B, e.encoder_seq_len, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
            "tokens": S_((B, S), jnp.int32),
            "labels": S_((B, S), jnp.int32),
        }

    def prefill_specs(shape: ShapeConfig):
        s = train_specs(shape)
        return {"frames": s["frames"], "tokens": s["tokens"]}

    def state_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        from repro.models.attention import cache_specs
        one = cache_specs(B, S, cfg.num_kv_heads, cfg.resolved_head_dim,
                          cfg.compute_dtype)
        stacked = jax.tree.map(
            lambda x: S_((cfg.num_layers,) + x.shape, x.dtype), one)
        return {"self": stacked,
                "enc": S_((B, e.encoder_seq_len, cfg.d_model),
                          jnp.dtype(cfg.compute_dtype))}

    def init_state(b, s):
        from repro.models.attention import init_cache
        one = init_cache(b, s, cfg.num_kv_heads, cfg.resolved_head_dim,
                         jnp.dtype(cfg.compute_dtype))
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape).copy(), one)
        return {"self": stacked,
                "enc": jnp.zeros((b, e.encoder_seq_len, cfg.d_model),
                                 jnp.dtype(cfg.compute_dtype))}

    return ModelBundle(
        cfg=cfg,
        specs=whisper.whisper_specs(cfg),
        loss_fn=functools.partial(whisper.whisper_loss, cfg),
        prefill_fn=lambda params, frames, tokens: whisper.whisper_prefill(
            cfg, params, frames, tokens),
        decode_fn=functools.partial(whisper.whisper_decode, cfg),
        train_input_specs=train_specs,
        prefill_input_specs=prefill_specs,
        decode_state_specs=state_specs,
        init_decode_state=init_state,
    )


def _build_pixtral(cfg: ModelConfig) -> ModelBundle:
    v = cfg.vlm
    n_img = v.num_image_tokens

    def train_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        s_text = max(S - n_img, 1)
        return {
            "tokens": S_((B, s_text), jnp.int32),
            "patches": S_((B, n_img, cfg.d_model), jnp.dtype(cfg.compute_dtype)),
            "labels": S_((B, s_text), jnp.int32),
        }

    def prefill_specs(shape: ShapeConfig):
        s = train_specs(shape)
        return {"tokens": s["tokens"], "patches": s["patches"]}

    return ModelBundle(
        cfg=cfg,
        specs=pixtral.pixtral_specs(cfg),
        loss_fn=functools.partial(pixtral.pixtral_loss, cfg),
        prefill_fn=lambda params, tokens, patches: pixtral.pixtral_prefill(
            cfg, params, tokens, patches),
        decode_fn=functools.partial(transformer.lm_decode, cfg),
        train_input_specs=train_specs,
        prefill_input_specs=prefill_specs,
        decode_state_specs=lambda shape: transformer.decode_cache_specs(
            cfg, shape.global_batch, shape.seq_len),
        init_decode_state=functools.partial(
            lambda cfg, b, s: transformer.init_decode_caches(cfg, b, s), cfg),
    )


_BUILDERS = {
    "dense": _build_lm,
    "moe": _build_lm,
    "hybrid": _build_rg,
    "ssm": _build_rwkv,
    "encdec": _build_whisper,
    "vlm": _build_pixtral,
}


def build(cfg: ModelConfig) -> ModelBundle:
    cfg.validate()
    return _BUILDERS[cfg.family](cfg)


# ---------------------------------------------------------------------------
# Analytic parameter / FLOP accounting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    bundle = build(cfg)
    total = 0
    for leaf in common.spec_leaves(bundle.specs):
        n = int(np.prod(leaf.shape))
        total += n
    if active_only and cfg.moe is not None:
        m = cfg.moe
        # each expert's FFN params (gate+up+down), counted per layer
        per_expert = 3 * cfg.d_model * m.d_ff_expert
        n_layers = cfg.num_layers
        inactive = (m.num_experts - m.top_k) * per_expert * n_layers
        total -= inactive
    return total


def embed_param_count(cfg: ModelConfig) -> int:
    n = cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        n *= 2
    return n


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS per step: 6·N·T (train), 2·N·T (prefill), 2·N·B (decode),
    with N = active params excluding the input embedding table (lm_head kept)."""
    n_active = count_params(cfg, active_only=True)
    n_active -= cfg.vocab_size * cfg.d_model          # input table is a gather
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch        # decode: 1 new token/seq
