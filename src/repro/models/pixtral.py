"""Pixtral-style VLM backbone: mistral-nemo decoder + stub vision frontend.

Per the assignment, ``[vlm]`` entries exercise the transformer backbone only:
``input_specs()`` provides precomputed patch embeddings [B, n_img, D]
(the pixtral-ViT tower is a stub).  Patch embeddings are projected through a
learned multimodal adapter and *prepended* to the token embeddings; training
labels over image positions are masked (-100 idiom).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, apply_norm, cast_tree, dot
from repro.models.transformer import (cross_entropy, decode_cache_specs,
                                      decoder_layer_apply, embed_lookup,
                                      init_decode_caches, lm_head, lm_specs,
                                      lm_forward)


def pixtral_specs(cfg):
    d = cfg.d_model
    specs = lm_specs(cfg)
    specs["adapter"] = {
        "w_in": ParamSpec((d, d), ("embed", "embed2")),
        "b_in": ParamSpec((d,), ("embed2",), init="zeros"),
    }
    return specs


def _prepend_patches(cfg, params, tokens, patches, cd):
    """Embed tokens, adapter-project patches, concatenate [img ; text]."""
    tok_emb = embed_lookup(cfg, params, tokens, cd)
    img = dot(patches.astype(cd), params["adapter"]["w_in"], cd)
    img = img + params["adapter"]["b_in"].astype(cd)
    return jnp.concatenate([img, tok_emb], axis=1)


def pixtral_loss(cfg, params, batch):
    """batch: {"tokens": [B,S_text], "patches": [B,n_img,D], "labels": [B,S_text]}"""
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    x = _prepend_patches(cfg, params, batch["tokens"], batch["patches"], cd)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    import functools
    from repro.models.transformer import _remat
    layer_fn = _remat(cfg, functools.partial(decoder_layer_apply, cfg))

    def body(carry, lp):
        x, aux = carry
        x, _, a = layer_fn(lp, x, positions)
        return (x, aux + a), None

    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    n_img = batch["patches"].shape[1]
    logits = lm_head(cfg, params, x[:, n_img:])       # predict text positions only
    return cross_entropy(logits, batch["labels"], cfg.vocab_size)


def pixtral_prefill(cfg, params, tokens, patches):
    """Prefill over [img ; text]; returns (last_logits, caches)."""
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    x0 = _prepend_patches(cfg, params, tokens, patches, cd)
    S = x0.shape[1]
    # reuse lm_forward's cache-collecting scan by substituting the embedding:
    # emulate via a token path is not possible (inputs are embeddings), so we
    # inline the same scan here.
    import functools
    from repro.models import attention as attn
    from repro.models import mlp as mlp_mod
    from repro.models import moe as moe_mod
    from repro.models.transformer import _fill_kv_cache
    B = x0.shape[0]
    positions = jnp.arange(S, dtype=jnp.int32)
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

    def body(x, lp):
        h = apply_norm(cfg, lp["ln1"], x)
        a, _ = attn.attention_apply(cfg, lp["attn"], h, positions)
        k = dot(h, lp["attn"]["wk"], cd).reshape(B, S, kv, hd)
        v = dot(h, lp["attn"]["wv"], cd).reshape(B, S, kv, hd)
        k = attn.apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
        cache = _fill_kv_cache(k, v, positions, S)
        x = x + a
        h2 = apply_norm(cfg, lp["ln2"], x)
        x = x + mlp_mod.mlp_apply(cfg, lp["ff"], h2)
        return x, cache

    x, caches = jax.lax.scan(body, x0, params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head(cfg, params, x[:, -1:])
    return logits[:, 0], caches
