"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local attention.

Layer pattern (RG-2B): (recurrent, recurrent, attention) repeating over 26
layers = 8 scanned super-blocks + 2 tail recurrent layers.

The RG-LRU recurrence (Griffin eq. 3-4):
    r_t = sigmoid(W_a x_t + b_a)                      (recurrence gate)
    i_t = sigmoid(W_x x_t + b_x)                      (input gate)
    a_t = exp(-c * softplus(Lambda) * r_t)            (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Train/prefill evaluates the recurrence with an *associative scan* (log-depth,
TPU-friendly); the Pallas kernel (kernels/rglru_scan) is the hand-tiled
alternative; decode is the one-step recurrence.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models.common import (ParamSpec, apply_norm, cast_tree, dot,
                                 maybe_wsc, norm_specs, stack_specs)
from repro.models.transformer import (cross_entropy, embed_lookup, embed_specs,
                                      lm_head)

P = jax.sharding.PartitionSpec
C_EXP = 8.0


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _lru_width(cfg) -> int:
    return cfg.rglru.lru_width or cfg.d_model


def recurrent_block_specs(cfg):
    d, w = cfg.d_model, _lru_width(cfg)
    cw = cfg.rglru.conv1d_width
    return {
        "ln": norm_specs(cfg),
        "w_x": ParamSpec((d, w), ("embed", "lru")),        # recurrence branch
        "w_gate": ParamSpec((d, w), ("embed", "lru")),     # gelu gate branch
        "conv_w": ParamSpec((cw, w), ("window", "lru"), init="small"),
        "conv_b": ParamSpec((w,), ("lru",), init="zeros"),
        "lru_lambda": ParamSpec((w,), ("lru",), init="normal"),
        # square gate matrices: shard the OUTPUT dim (matches u's sharding)
        "lru_wa": ParamSpec((w, w), ("lru_in", "lru")),
        "lru_ba": ParamSpec((w,), ("lru",), init="zeros"),
        "lru_wx": ParamSpec((w, w), ("lru_in", "lru")),
        "lru_bx": ParamSpec((w,), ("lru",), init="zeros"),
        "w_out": ParamSpec((w, d), ("lru", "embed2")),
        "ln2": norm_specs(cfg),
        "mlp": mlp_mod.mlp_specs(cfg),
    }


def attention_block_specs(cfg):
    return {"ln": norm_specs(cfg), "attn": attn.attention_specs(cfg),
            "ln2": norm_specs(cfg), "mlp": mlp_mod.mlp_specs(cfg)}


def super_block_specs(cfg):
    return {"rec1": recurrent_block_specs(cfg),
            "rec2": recurrent_block_specs(cfg),
            "attn": attention_block_specs(cfg)}


def rg_specs(cfg):
    n_super, n_tail = divmod(cfg.num_layers, 3)
    specs = {
        "embed": embed_specs(cfg),
        "blocks": stack_specs(super_block_specs(cfg), n_super),
        "final_norm": norm_specs(cfg),
    }
    if n_tail:
        specs["tail"] = stack_specs(recurrent_block_specs(cfg), n_tail)
    if not cfg.tie_embeddings:
        from repro.models.transformer import head_specs
        specs["lm_head"] = head_specs(cfg)
    return specs


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------

def _lru_gates(p, x):
    """x: [B,S,W] fp32 -> (log_a, gated_input) both [B,S,W] fp32."""
    x32 = x.astype(jnp.float32)
    r = jax.nn.sigmoid(x32 @ p["lru_wa"].astype(jnp.float32) + p["lru_ba"])
    i = jax.nn.sigmoid(x32 @ p["lru_wx"].astype(jnp.float32) + p["lru_bx"])
    log_a = -C_EXP * jax.nn.softplus(p["lru_lambda"].astype(jnp.float32)) * r
    a2 = jnp.exp(2.0 * log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * (i * x32)
    return log_a, gated


def rg_lru_scan(p, x, h0=None, use_pallas: bool = False):
    """Associative-scan evaluation. x: [B,S,W]; h0: [B,W] or None.

    Returns (y [B,S,W] in x.dtype, h_last [B,W] fp32).
    """
    log_a, gated = _lru_gates(p, x)
    if h0 is not None:
        # fold the incoming state in as a virtual step 0
        log_a = jnp.concatenate([jnp.zeros_like(log_a[:, :1]), log_a], axis=1)
        gated = jnp.concatenate([h0[:, None, :].astype(jnp.float32), gated], axis=1)
    if use_pallas:
        from repro.kernels.rglru_scan import ops as lru_ops
        h = lru_ops.lru_scan(log_a, gated)
    else:
        def combine(c1, c2):
            (la1, g1), (la2, g2) = c1, c2
            return la1 + la2, g1 * jnp.exp(la2) + g2
        _, h = jax.lax.associative_scan(combine, (log_a, gated), axis=1)
    if h0 is not None:
        h = h[:, 1:]
    return h.astype(x.dtype), h[:, -1].astype(jnp.float32)


def rg_lru_step(p, x_t, h_prev):
    """One decode step. x_t: [B,W]; h_prev: [B,W] fp32."""
    log_a, gated = _lru_gates(p, x_t[:, None, :])
    h = jnp.exp(log_a[:, 0]) * h_prev + gated[:, 0]
    return h.astype(x_t.dtype), h


def causal_conv1d(p, x, state=None):
    """Depthwise causal conv, width cw. x: [B,S,W]; state: [B,cw-1,W] or None.

    Returns (y [B,S,W], new_state [B,cw-1,W])."""
    cw = p["conv_w"].shape[0]
    B, S, W = x.shape
    if state is None:
        state = jnp.zeros((B, cw - 1, W), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)                      # [B,S+cw-1,W]
    y = sum(xp[:, i:i + S] * p["conv_w"][i].astype(x.dtype) for i in range(cw))
    y = y + p["conv_b"].astype(x.dtype)
    return y, xp[:, -(cw - 1):]


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def recurrent_block_apply(cfg, p, x, state=None, use_pallas=False):
    """state: {"conv": [B,cw-1,W], "h": [B,W] fp32} or None (train/prefill
    from zero state).  Returns (x, new_state_or_None)."""
    cd = x.dtype
    h_in = apply_norm(cfg, p["ln"], x)
    u = dot(h_in, p["w_x"], cd)
    u = maybe_wsc(u, P(None, None, "model"))
    gate = jax.nn.gelu(dot(h_in, p["w_gate"], cd))
    conv_state = state["conv"] if state is not None else None
    u, new_conv = causal_conv1d(p, u, conv_state)
    if state is None:
        y, h_last = rg_lru_scan(p, u, use_pallas=use_pallas)
        new_state = {"conv": new_conv, "h": h_last}
    else:
        y, h_last = rg_lru_step(p, u[:, 0], state["h"])
        y = y[:, None, :]
        new_state = {"conv": new_conv, "h": h_last}
    x = x + dot(y * gate, p["w_out"], cd)
    h2 = apply_norm(cfg, p["ln2"], x)
    x = x + mlp_mod.mlp_apply(cfg, p["mlp"], h2)
    return x, new_state


def attention_block_apply(cfg, p, x, positions, cache=None, use_pallas=False):
    h = apply_norm(cfg, p["ln"], x)
    a, new_cache = attn.attention_apply(cfg, p["attn"], h, positions,
                                        cache=cache, use_pallas=use_pallas)
    x = x + a
    h2 = apply_norm(cfg, p["ln2"], x)
    x = x + mlp_mod.mlp_apply(cfg, p["mlp"], h2)
    return x, new_cache


def super_block_apply(cfg, p, x, positions, states=None, use_pallas=False):
    s1 = states["rec1"] if states else None
    s2 = states["rec2"] if states else None
    sa = states["attn"] if states else None
    x, n1 = recurrent_block_apply(cfg, p["rec1"], x, s1, use_pallas)
    x, n2 = recurrent_block_apply(cfg, p["rec2"], x, s2, use_pallas)
    x, na = attention_block_apply(cfg, p["attn"], x, positions, sa, use_pallas)
    if states is None:
        return x, None
    return x, {"rec1": n1, "rec2": n2, "attn": na}


# ---------------------------------------------------------------------------
# Model entry points
# ---------------------------------------------------------------------------

def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    return jax.checkpoint(fn)


def rg_forward(cfg, params, tokens, use_pallas=False):
    cd = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    x = embed_lookup(cfg, params, tokens, cd)

    block_fn = _remat(cfg, functools.partial(
        super_block_apply, cfg, positions=positions, use_pallas=use_pallas))

    def body(x, bp):
        x, _ = block_fn(bp, x)
        return x, None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    if "tail" in params:
        tail_fn = _remat(cfg, functools.partial(
            recurrent_block_apply, cfg, use_pallas=use_pallas))

        def tbody(x, tp):
            x, _ = tail_fn(tp, x)
            return x, None
        x, _ = jax.lax.scan(tbody, x, params["tail"])
    return apply_norm(cfg, params["final_norm"], x)


def rg_loss(cfg, params, batch, *, use_pallas=False):
    params = cast_tree(params, cfg.compute_dtype)
    x = rg_forward(cfg, params, batch["tokens"], use_pallas=use_pallas)
    logits = lm_head(cfg, params, x)
    return cross_entropy(logits, batch["labels"], cfg.vocab_size)


# --- decode ----------------------------------------------------------------

def _rec_state_init(cfg, batch):
    w, cw = _lru_width(cfg), cfg.rglru.conv1d_width
    cd = jnp.dtype(cfg.compute_dtype)
    return {"conv": jnp.zeros((batch, cw - 1, w), cd),
            "h": jnp.zeros((batch, w), jnp.float32)}


def rg_init_states(cfg, batch: int, seq_len: int):
    n_super, n_tail = divmod(cfg.num_layers, 3)
    cd = jnp.dtype(cfg.compute_dtype)
    win = cfg.rglru.attention_window
    cache = attn.init_cache(batch, min(seq_len, win), cfg.num_kv_heads,
                            cfg.resolved_head_dim, cd)
    one = {"rec1": _rec_state_init(cfg, batch),
           "rec2": _rec_state_init(cfg, batch), "attn": cache}
    states = {"blocks": jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_super,) + x.shape).copy(), one)}
    if n_tail:
        states["tail"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_tail,) + x.shape).copy(),
            _rec_state_init(cfg, batch))
    return states


def rg_state_specs(cfg, batch: int, seq_len: int):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        jax.eval_shape(lambda: rg_init_states(cfg, batch, seq_len)))


def rg_decode(cfg, params, tokens, states):
    """tokens [B,1] + states -> (logits [B,V], new_states)."""
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    index = states["blocks"]["attn"]["index"][0]
    positions = jnp.full((B, 1), 0, jnp.int32) + index
    x = embed_lookup(cfg, params, tokens, cd)

    def body(x, xs):
        bp, st = xs
        x, new_st = super_block_apply(cfg, bp, x, positions, st)
        return x, new_st

    x, new_blocks = jax.lax.scan(body, x, (params["blocks"], states["blocks"]))
    new_states = {"blocks": new_blocks}
    if "tail" in params:
        def tbody(x, xs):
            tp, st = xs
            x, new_st = recurrent_block_apply(cfg, tp, x, st)
            return x, new_st
        x, new_tail = jax.lax.scan(tbody, x, (params["tail"], states["tail"]))
        new_states["tail"] = new_tail
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head(cfg, params, x)
    return logits[:, 0], new_states


def rg_prefill(cfg, params, tokens, *, cache_len: int = 0, use_pallas=False):
    """Prefill: full forward while materializing final recurrent states and
    the local-attention ring caches.  Returns (last_logits [B,V], states).

    ``cache_len`` sets the ring-cache capacity (still bounded by the
    attention window); 0 keeps the prompt-length cache of the demo path.
    The serving engine passes its pool capacity so prefill states drop
    straight into a KV slot without reshaping."""
    params = cast_tree(params, cfg.compute_dtype)
    cd = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    win = cfg.rglru.attention_window
    x = embed_lookup(cfg, params, tokens, cd)

    def rec_prefill(p, x, use_pallas=use_pallas):
        h_in = apply_norm(cfg, p["ln"], x)
        u = dot(h_in, p["w_x"], cd)
        gate = jax.nn.gelu(dot(h_in, p["w_gate"], cd))
        u, conv_state = causal_conv1d(p, u)
        y, h_last = rg_lru_scan(p, u, use_pallas=use_pallas)
        x = x + dot(y * gate, p["w_out"], cd)
        x = x + mlp_mod.mlp_apply(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
        return x, {"conv": conv_state, "h": h_last}

    def attn_prefill(p, x):
        from repro.models.transformer import _fill_kv_cache
        h = apply_norm(cfg, p["ln"], x)
        a, _ = attn.attention_apply(cfg, p["attn"], h, positions,
                                    use_pallas=use_pallas)
        k = dot(h, p["attn"]["wk"], cd).reshape(B, S, cfg.num_kv_heads, -1)
        v = dot(h, p["attn"]["wv"], cd).reshape(B, S, cfg.num_kv_heads, -1)
        k = attn.apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
        cache = _fill_kv_cache(k, v, positions, min(cache_len or S, win))
        x = x + a
        x = x + mlp_mod.mlp_apply(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
        return x, cache

    def body(x, bp):
        x, s1 = rec_prefill(bp["rec1"], x)
        x, s2 = rec_prefill(bp["rec2"], x)
        x, ca = attn_prefill(bp["attn"], x)
        return x, {"rec1": s1, "rec2": s2, "attn": ca}

    x, blocks = jax.lax.scan(body, x, params["blocks"])
    states = {"blocks": blocks}
    if "tail" in params:
        def tbody(x, tp):
            return rec_prefill(tp, x)
        x, tail = jax.lax.scan(tbody, x, params["tail"])
        states["tail"] = tail
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_head(cfg, params, x[:, -1:])
    return logits[:, 0], states
