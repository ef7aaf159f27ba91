"""Mixture-of-Experts substrate (token-choice top-k, GShard-style dispatch).

TPU adaptation: dispatch/combine are *dense grouped einsums* with a per-group
capacity limit — no dynamic shapes, MXU-friendly, and the expert dimension of
the dispatch buffer is pinned to the "model" mesh axis so GSPMD emits the
expert-parallel all-to-all exactly where MPI_Alltoall would sit in an MPI
implementation (paper §II-B maps collectives, not point-to-point, onto scale).

Expert placement (see common.rules_for):
  * num_experts % model_axis == 0  -> expert-parallel ("expert" -> "model")
  * otherwise                      -> per-expert tensor-parallel
    ("expert_mlp" -> "model"), e.g. Mixtral's 8 experts on a 16-way axis.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, activation, dot, maybe_wsc

P = jax.sharding.PartitionSpec


def moe_specs(cfg):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    specs = {
        "router": ParamSpec((d, m.num_experts), ("embed", "expert"), init="small"),
        "w_gate": ParamSpec((m.num_experts, d, f), ("expert", "embed", "expert_mlp")),
        "w_up": ParamSpec((m.num_experts, d, f), ("expert", "embed", "expert_mlp")),
        "w_down": ParamSpec((m.num_experts, f, d), ("expert", "expert_mlp", "embed2")),
    }
    if m.num_shared_experts:
        fs = m.d_ff_shared or f * m.num_shared_experts
        specs["shared"] = {
            "w_gate": ParamSpec((d, fs), ("embed", "mlp")),
            "w_up": ParamSpec((d, fs), ("embed", "mlp")),
            "w_down": ParamSpec((fs, d), ("mlp", "embed2")),
        }
    return specs


def _choose_group_size(n_tokens: int, num_experts: int) -> int:
    """Pick a dispatch group size keeping the [g,E,C] combine tensor modest."""
    for g in (4096, 2048, 1024, 512, 256, 128):
        if n_tokens % g == 0 and g * num_experts <= 4096 * 16:
            return g
    return n_tokens


@jax.named_scope("mlp")
def moe_apply(cfg, p, x, *, n_valid=None,
              per_token: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,D] -> (y [B,S,D], aux_loss scalar fp32).

    ``n_valid`` (traced, serving prefill): positions >= n_valid along S are
    a masked bucket tail — their router assignments are zeroed *before*
    capacity accounting, so padding can never displace a real token from
    an expert (their own outputs are garbage either way; the engine masks
    them downstream).  ``per_token`` (serving paths): dispatch in groups of
    one token — C = 1 then admits every token's full top-k, so routing is
    *drop-free* and strictly per-token.  Training keeps GShard capacity
    semantics; serving uses per_token everywhere because capacity
    truncation couples tokens across group shapes (bucket widths, chunk
    boundaries, concurrently decoding slots, prefix-cache-skipped
    prefixes), which would break the engine's token-identity guarantee —
    warm != cold, paged != slotted — whenever a drop binds.  The price is
    the dense dispatch running E instead of ~K·cf expert rows per token at
    serve time; a ragged grouped-GEMM serve kernel is the ROADMAP answer.
    """
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    cd = x.dtype
    N = B * S
    g = 1 if per_token else _choose_group_size(N, E)
    G = N // g
    C = max(int(g * K / E * m.capacity_factor), 1)
    C = min(C, g)

    xf = x.reshape(G, g, D)
    logits = jnp.einsum("gtd,de->gte", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))           # [G,g,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, sel = jax.lax.top_k(probs, K)                        # [G,g,K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)           # renormalize

    # --- capacity assignment (choice-major priority, GShard) ---------------
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32)                # [G,g,K,E]
    if n_valid is not None:
        # bucket-tail padding routes nowhere: it must not consume expert
        # capacity (a padding row displacing a real token would make the
        # compiled bucket width leak into valid tokens' outputs)
        vmask = jnp.broadcast_to(
            (jnp.arange(S) < n_valid)[None, :], (B, S)).reshape(G, g)
        onehot = onehot * vmask[..., None, None].astype(onehot.dtype)
    prio = onehot.transpose(0, 2, 1, 3).reshape(G, K * g, E)        # choice-major
    pos = jnp.cumsum(prio, axis=1) * prio - 1                       # position in expert
    pos = pos.reshape(G, K, g, E).transpose(0, 2, 1, 3)             # [G,g,K,E]
    keep = (pos >= 0) & (pos < C)
    pos = jnp.where(keep, pos, 0)
    slot_onehot = jax.nn.one_hot(pos, C, dtype=cd) * keep[..., None].astype(cd)
    # combine[g,t,E,C] = Σ_k gate * slot
    combine = jnp.einsum("gtke,gtkec->gtec",
                         (gate_vals[..., None] * onehot.astype(jnp.float32)).astype(cd),
                         slot_onehot)                               # [G,g,E,C]
    dispatch = (combine > 0).astype(cd)

    # --- dispatch -> expert FFN -> combine ---------------------------------
    xe = jnp.einsum("gtd,gtec->gecd", xf, dispatch)                 # [G,E,C,D]
    xe = maybe_wsc(xe, P(None, "model", None, None))                # pin EP all-to-all
    act = activation(cfg.act)
    h = act(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"].astype(cd))) * \
        jnp.einsum("gecd,edf->gecf", xe, p["w_up"].astype(cd))
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(cd))    # [G,E,C,D]
    y = jnp.einsum("gecd,gtec->gtd", ye, combine)                   # [G,g,D]
    y = y.reshape(B, S, D)

    if m.num_shared_experts:
        sh = p["shared"]
        hs = act(dot(x, sh["w_gate"], cd)) * dot(x, sh["w_up"], cd)
        y = y + dot(hs, sh["w_down"], cd)

    # --- Switch load-balance auxiliary loss --------------------------------
    frac = jnp.mean(onehot.astype(jnp.float32), axis=(1, 2))        # [G,E] token fraction·K
    pmean = jnp.mean(probs, axis=1)                                 # [G,E]
    aux = E * jnp.mean(jnp.sum(frac * pmean, axis=-1)) / K
    return y, aux.astype(jnp.float32)
