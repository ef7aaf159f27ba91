"""Decoder-only LM assembly: embeddings + scanned layers + head.

Covers the dense (GQA/SWA), MoE, and MLA families.  Hybrid (RG-LRU), SSM
(RWKV6), enc-dec (whisper) and VLM (pixtral) live in their own modules but
reuse the helpers here.

Three entry points lowered by the launcher:
  * ``lm_loss``       — train_* cells (tokens -> scalar loss)
  * ``lm_prefill``    — prefill_* cells (tokens -> last-token logits + caches)
  * ``lm_decode``     — decode_* / long_* cells (1 token + caches -> logits)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.common import (ParamSpec, apply_norm, cast_tree, dot,
                                 maybe_wsc, norm_specs, stack_specs)

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def embed_specs(cfg):
    return ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab_table", "embed"),
                     init="embed")


def head_specs(cfg):
    return ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))


def decoder_layer_specs(cfg):
    a = attn.mla_specs(cfg) if cfg.attn_kind == "mla" else attn.attention_specs(cfg)
    ff = moe_mod.moe_specs(cfg) if cfg.moe is not None else mlp_mod.mlp_specs(cfg)
    return {"ln1": norm_specs(cfg), "attn": a, "ln2": norm_specs(cfg), "ff": ff}


def lm_specs(cfg):
    specs = {
        "embed": embed_specs(cfg),
        "layers": stack_specs(decoder_layer_specs(cfg), cfg.num_layers),
        "final_norm": norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = head_specs(cfg)
    return specs


# ---------------------------------------------------------------------------
# Layer apply
# ---------------------------------------------------------------------------

def decoder_layer_apply(cfg, p, x, positions, cache=None, use_pallas=False):
    """Returns (x, new_cache, aux_loss)."""
    h = apply_norm(cfg, p["ln1"], x)
    if cfg.attn_kind == "mla":
        a, new_cache = attn.mla_apply(cfg, p["attn"], h, positions, cache=cache)
    else:
        a, new_cache = attn.attention_apply(cfg, p["attn"], h, positions,
                                            cache=cache, use_pallas=use_pallas)
    x = x + a
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe is not None:
        f, aux = moe_mod.moe_apply(cfg, p["ff"], h)
    else:
        f, aux = mlp_mod.mlp_apply(cfg, p["ff"], h), jnp.zeros((), jnp.float32)
    x = x + f
    x = maybe_wsc(x, P(None, None, None))
    return x, new_cache, aux


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_lookup(cfg, params, tokens, compute_dtype):
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
    return x


@jax.named_scope("lm_head")
def lm_head(cfg, params, x):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dot(x, w, x.dtype)
    return maybe_wsc(logits, P(None, None, "model"))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def lm_forward(cfg, params, tokens, *, collect_cache: bool = False,
               cache_len: int = 0, use_pallas: bool = False, n_valid=None,
               moe_dropless: bool = False):
    """tokens [B,S] -> (logits [B,S,V], caches_or_None, aux).

    ``n_valid`` (traced scalar, cache-collection path only) marks a masked
    bucket tail: tokens at positions >= n_valid are padding — their cache
    slots carry pos = -1 (decode never attends them) and the cache index is
    n_valid, so one compiled shape serves every prompt length in a bucket.
    Causality already keeps tail padding out of the valid tokens' outputs.

    ``moe_dropless`` (serving prefill): route MoE per token with no
    capacity truncation, so the serving engine's token-identity guarantee
    survives bucket widths / chunk boundaries / prefix-cache skips (see
    ``moe_apply``).  Training and the roofline prefill cells keep GShard
    capacity semantics.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_lookup(cfg, params, tokens, cd)
    x = maybe_wsc(x, P(None, None, None))

    layer_fn = _remat(cfg, functools.partial(
        decoder_layer_apply, cfg, use_pallas=use_pallas))

    if not collect_cache:
        def body(carry, lp):
            x, aux = carry
            x, _, a = layer_fn(lp, x, positions)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        caches = None
    else:
        kv = cfg.num_kv_heads
        hd = cfg.resolved_head_dim

        def body(carry, lp):
            x, aux = carry
            h = apply_norm(cfg, lp["ln1"], x)
            if cfg.attn_kind == "mla":
                # run expanded attention, stash latent cache
                a, _ = attn.mla_apply(cfg, lp["attn"], h, positions)
                from repro.models.common import rms_norm
                dkv = dot(h, lp["attn"]["w_dkv"], cd)
                ckv, krope = jnp.split(dkv, [cfg.mla.kv_lora_rank], axis=-1)
                ckv = rms_norm(ckv, lp["attn"]["kv_norm"], cfg.norm_eps)
                krope = attn.apply_rope(
                    krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
                cache_y = _fill_latent_cache(ckv, krope, positions, cache_len,
                                             n_valid)
            else:
                a, new_c = attn.attention_apply(cfg, lp["attn"], h, positions,
                                                use_pallas=use_pallas)
                # recompute k/v once more is avoided: attention_apply already
                # projected them; for cache collection we project again below —
                # cheap relative to attention itself, and keeps apply pure.
                q_unused = None
                k = dot(h, lp["attn"]["wk"], cd)
                v = dot(h, lp["attn"]["wv"], cd)
                if cfg.qkv_bias:
                    k = k + lp["attn"]["bk"].astype(cd)
                    v = v + lp["attn"]["bv"].astype(cd)
                k = k.reshape(B, S, kv, hd)
                v = v.reshape(B, S, kv, hd)
                k = attn.apply_rope(k, positions, cfg.rope_theta,
                                    cfg.rotary_dim)
                cache_y = _fill_kv_cache(k, v, positions, cache_len, n_valid)
            x = x + a
            h2 = apply_norm(cfg, lp["ln2"], x)
            if cfg.moe is not None:
                # bucketed serving prefill: padding must not consume
                # expert capacity, and serving routes drop-free per token
                # (see moe_apply's n_valid / per_token)
                f, a2 = moe_mod.moe_apply(cfg, lp["ff"], h2, n_valid=n_valid,
                                          per_token=moe_dropless)
            else:
                f, a2 = mlp_mod.mlp_apply(cfg, lp["ff"], h2), jnp.zeros((), jnp.float32)
            return (x + f, aux + a2), cache_y

        (x, aux), caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"])

    x = apply_norm(cfg, params["final_norm"], x)
    return x, caches, aux


def _fill_kv_cache(k, v, positions, cache_len: int, n_valid=None):
    """Build a ring cache from prefill k/v (keep the last cache_len tokens).

    With ``n_valid`` (masked bucket tail; requires S <= cache_len so padding
    cannot ring-wrap onto valid slots) padding entries carry pos = -1 and
    ``index`` = n_valid — decode masks them exactly like never-written slots.
    """
    B, S, KV, hd = k.shape
    L = min(cache_len, S) if cache_len else S
    ks = k[:, S - L:]
    vs = v[:, S - L:]
    pos = positions[S - L:]
    slots = pos % (cache_len or S)
    Lc = cache_len or S
    ck = jnp.zeros((B, Lc, KV, hd), k.dtype).at[:, slots].set(ks)
    cv = jnp.zeros((B, Lc, KV, hd), v.dtype).at[:, slots].set(vs)
    if n_valid is None:
        cpos = jnp.full((Lc,), -1, jnp.int32).at[slots].set(pos)
        index = jnp.asarray(S, jnp.int32)
    else:
        cpos = jnp.full((Lc,), -1, jnp.int32).at[slots].set(
            jnp.where(pos < n_valid, pos, -1))
        index = jnp.asarray(n_valid, jnp.int32)
    return {"k": ck, "v": cv, "pos": cpos, "index": index}


def _fill_latent_cache(ckv, krope, positions, cache_len: int, n_valid=None):
    B, S, R = ckv.shape
    Lc = cache_len or S
    L = min(Lc, S)
    pos = positions[S - L:]
    slots = pos % Lc
    c1 = jnp.zeros((B, Lc, R), ckv.dtype).at[:, slots].set(ckv[:, S - L:])
    c2 = jnp.zeros((B, Lc, krope.shape[-1]), krope.dtype).at[:, slots].set(krope[:, S - L:])
    if n_valid is None:
        cpos = jnp.full((Lc,), -1, jnp.int32).at[slots].set(pos)
        index = jnp.asarray(S, jnp.int32)
    else:
        cpos = jnp.full((Lc,), -1, jnp.int32).at[slots].set(
            jnp.where(pos < n_valid, pos, -1))
        index = jnp.asarray(n_valid, jnp.int32)
    return {"ckv": c1, "krope": c2, "pos": cpos, "index": index}


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, vocab: int):
    """Mean token cross-entropy in fp32 (labels == -100 are masked)."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    labels = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def lm_loss(cfg, params, batch, *, use_pallas: bool = False):
    """batch: {"tokens": [B,S], "labels": [B,S]} -> scalar fp32 loss."""
    params = cast_tree(params, cfg.compute_dtype)
    x, _, aux = lm_forward(cfg, params, batch["tokens"], use_pallas=use_pallas)
    logits = lm_head(cfg, params, x)
    loss = cross_entropy(logits, batch["labels"], cfg.vocab_size)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss


def lm_prefill(cfg, params, tokens, *, cache_len: int = 0,
               use_pallas: bool = False, n_valid=None,
               moe_dropless: bool = False):
    """tokens [B,S] -> (last_logits [B,V], caches).

    ``n_valid`` (traced): S is a padded power-of-two bucket and only the
    first n_valid tokens are real — the cache masks the tail and the
    returned logits are the n_valid-th token's, so one compiled shape
    serves every prompt length that rounds up to the same bucket.
    ``moe_dropless`` selects the serving engine's per-token (no capacity
    truncation) MoE routing — see lm_forward.
    """
    params = cast_tree(params, cfg.compute_dtype)
    x, caches, _ = lm_forward(cfg, params, tokens, collect_cache=True,
                              cache_len=cache_len or tokens.shape[1],
                              use_pallas=use_pallas, n_valid=n_valid,
                              moe_dropless=moe_dropless)
    if n_valid is None:
        last = x[:, -1:]
    else:
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(n_valid, jnp.int32) - 1, 1, axis=1)
    logits = lm_head(cfg, params, last)
    return logits[:, 0], caches


def lm_paged_prefill(cfg, params, tokens, state, *, use_pallas: bool = False):
    """Prefill one request's (suffix) chunk straight into the paged pool
    (forward body shared with ``lm_paged_verify``).

    tokens [1, S] — S is a padded power-of-two bucket; state:
      * ``pages``      {"k","v"}: [L, P, KV, ps, hd] — global page pool
      * ``page_table`` [n] int32 — this request's page-table row
      * ``start``      traced scalar — tokens already cached (shared prefix
        pages + earlier chunks); the chunk holds positions start..start+S-1
      * ``n_valid``    traced scalar — real tokens in the chunk (the bucket
        tail past it is masked and written to the trash page)

    Returns (logits [1, V] of the last *valid* token, new_pages).  One
    compiled shape per bucket covers every (prompt_len, prefix_len, chunk)
    combination — the dispatch that used to jit per prompt length.
    ``use_pallas`` selects the scalar-prefetched Pallas chunked-prefill
    kernels (contiguous / ring / absorbed-MLA variants; HBM traffic ~
    pages actually held) over the traced whole-table gather.

    Dispatches on the family's page layout: per-head k/v pages (full
    attention's contiguous pages and swa/local's ring-wrapped window
    pages) vs MLA's latent ckv/krope pages.  Quantized (int8) pools add
    ``k_scale``/``v_scale`` leaves to each layer's kv dict — they ride the
    same ``lax.scan`` over layers with no structural change here; the
    attention layer quantizes on write and fuses dequant into the scores.
    """
    x, n_valid, new_pages = _paged_forward(cfg, params, tokens, state,
                                           use_pallas=use_pallas)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    logits = lm_head(cfg, params, last)
    return logits[:, 0], new_pages


def lm_paged_verify(cfg, params, tokens, state, *, use_pallas: bool = False):
    """Speculative-decode verify forward: the drafted span runs through
    the same paged-prefill scatter (accepted tokens' K/V lands straight
    in the request's pages) but the head runs over *every* position —
    logits [S, V], one row per input token, row ``j`` predicting
    sequence index ``start + 1 + j``.  The engine replays its sampler
    over these rows to decide the accepted prefix; invalid tail rows
    (``j >= n_valid``) are masked into the trash page exactly like a
    bucketed prefill tail and their logits are simply ignored.
    ``use_pallas`` routes the drafted span's chunk-shaped attention
    through the same Pallas prefill kernels as ``lm_paged_prefill``."""
    x, _, new_pages = _paged_forward(cfg, params, tokens, state,
                                     use_pallas=use_pallas)
    logits = lm_head(cfg, params, x)
    return logits[0], new_pages


def _paged_forward(cfg, params, tokens, state, *, use_pallas: bool = False):
    """Shared paged prefill/verify body -> (x [1,S,d], n_valid, new_pages)."""
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    S = tokens.shape[1]
    start = jnp.asarray(state["start"], jnp.int32)
    n_valid = jnp.asarray(state["n_valid"], jnp.int32)
    positions = start + jnp.arange(S, dtype=jnp.int32)
    x = embed_lookup(cfg, params, tokens, cd)

    def body(x, layer_in):
        lp, kv = layer_in
        h = apply_norm(cfg, lp["ln1"], x)
        if cfg.attn_kind == "mla":
            a, new_kv = attn.paged_mla_prefill_apply(
                cfg, lp["attn"], h, positions, kv, state["page_table"],
                start, n_valid, use_pallas=use_pallas)
        else:
            a, new_kv = attn.paged_prefill_apply(
                cfg, lp["attn"], h, positions, kv, state["page_table"],
                start, n_valid, use_pallas=use_pallas)
        x = x + a
        h = apply_norm(cfg, lp["ln2"], x)
        if cfg.moe is not None:
            f, _ = moe_mod.moe_apply(cfg, lp["ff"], h, n_valid=n_valid,
                                     per_token=True)
        else:
            f = mlp_mod.mlp_apply(cfg, lp["ff"], h)
        x = x + f
        x = maybe_wsc(x, P(None, None, None))
        return x, new_kv

    x, new_pages = jax.lax.scan(body, x, (params["layers"], state["pages"]))
    x = apply_norm(cfg, params["final_norm"], x)
    return x, n_valid, new_pages


def lm_decode(cfg, params, tokens, caches):
    """One decode step. tokens [B,1]; caches stacked [L,...] trees.

    Returns (logits [B,V], new_caches)."""
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    index = caches["index"][0] if "index" in caches else caches["ckv_index"]
    positions = jnp.full((B, 1), 0, jnp.int32) + index
    x = embed_lookup(cfg, params, tokens, cd)

    def body(x, layer_in):
        lp, cache = layer_in
        x, new_cache, _ = decoder_layer_apply(cfg, lp, x, positions, cache=cache)
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head(cfg, params, x)
    return logits[:, 0], new_caches


def paged_decoder_layer_apply(cfg, p, x, positions, kv, page_table, lengths,
                              use_pallas=False):
    """Decode-step layer over a shared paged KV pool.  Returns (x, new_kv).

    Dispatches on the family's page layout: per-head k/v pages for full and
    sliding-window/local attention (``paged_attention_apply``), latent
    ckv/krope pages for MLA (``paged_mla_attention_apply``)."""
    h = apply_norm(cfg, p["ln1"], x)
    if cfg.attn_kind == "mla":
        a, new_kv = attn.paged_mla_attention_apply(
            cfg, p["attn"], h, positions, kv, page_table, lengths,
            use_pallas=use_pallas)
    else:
        a, new_kv = attn.paged_attention_apply(cfg, p["attn"], h, positions,
                                               kv, page_table, lengths,
                                               use_pallas=use_pallas)
    x = x + a
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe is not None:
        # per-token groups: concurrently decoding slots must never compete
        # for expert capacity (slot isolation == the vmapped slotted path)
        f, _ = moe_mod.moe_apply(cfg, p["ff"], h, per_token=True)
    else:
        f = mlp_mod.mlp_apply(cfg, p["ff"], h)
    x = x + f
    x = maybe_wsc(x, P(None, None, None))
    return x, new_kv


def lm_paged_decode(cfg, params, tokens, state, *, use_pallas: bool = False):
    """One decode step for *all* serving slots against a paged KV pool.

    Unlike ``lm_decode`` (one private ring cache per sequence, vmapped by
    the engine), the pool is shared, so the whole slot batch runs as one
    call.  tokens [slots, 1]; state:
      * ``pages``      {"k","v"}: [L, P, KV, ps, hd] — global page pool
      * ``page_table`` [slots, n] int32 — per-slot page ids (0 = trash)
      * ``pos``        [slots] int32 — tokens already cached per slot
        (= the position this step's token is written at)

    Returns (logits [slots, V], new_pages).  The page layout is the
    family's (``repro.serving.layouts``): contiguous k/v pages for full
    attention, ring-wrapped window pages for swa/local (the position
    mapping and window mask live in the paged-attention kernel/ref), and
    latent ckv/krope pages for MLA (absorbed decode).  Int8 pools carry
    ``k_scale``/``v_scale`` leaves per layer (quantize-on-append, dequant
    fused into the attention math) — transparent to the scan over layers.
    """
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    lengths = state["pos"]
    positions = lengths[:, None]
    x = embed_lookup(cfg, params, tokens, cd)

    def body(x, layer_in):
        lp, kv = layer_in
        x, new_kv = paged_decoder_layer_apply(
            cfg, lp, x, positions, kv, state["page_table"], lengths,
            use_pallas=use_pallas)
        return x, new_kv

    x, new_pages = jax.lax.scan(body, x, (params["layers"], state["pages"]))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head(cfg, params, x)
    return logits[:, 0], new_pages


def decode_cache_len(cfg, seq_len: int) -> int:
    """Ring-buffer length: bounded by the attention window when subquadratic."""
    if cfg.attn_kind in ("swa", "local") and cfg.window:
        return min(seq_len, cfg.window)
    return seq_len


def init_decode_caches(cfg, batch: int, seq_len: int):
    """Stacked [L,...] cache tree for lm_decode."""
    Lc = decode_cache_len(cfg, seq_len)
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.attn_kind == "mla":
        one = attn.mla_init_cache(batch, Lc, cfg, cd)
    else:
        one = attn.init_cache(batch, Lc, cfg.num_kv_heads,
                              cfg.resolved_head_dim, cd)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape).copy(), one)


def decode_cache_specs(cfg, batch: int, seq_len: int):
    Lc = decode_cache_len(cfg, seq_len)
    cd = cfg.compute_dtype
    if cfg.attn_kind == "mla":
        one = attn.mla_cache_specs(batch, Lc, cfg, cd)
    else:
        one = attn.cache_specs(batch, Lc, cfg.num_kv_heads,
                               cfg.resolved_head_dim, cd)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape, s.dtype), one)
