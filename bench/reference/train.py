"""Plain float32 reference of data-parallel training of a dense decoder:
the benchmark's yardstick for a training cell's ``correct``.  It imports
nothing of the program under test (only ``dense.py`` beside it).

It follows the published StableLM 2 block: pre-norm LayerNorm with gain
and bias, attention with a bias on the query, key and value projections
and rotary embedding on the first ``partial_rotary_factor`` of each head's
dims, a SiLU-gated feed-forward, an untied LM head; the loss is the mean
next-token cross-entropy.  The gradient comes from ``jax.grad``; Adam
(with clipping by global norm, as the configuration's ``optimizer`` block
states) updates float32 parameters.

It is *sequential*: one device takes the whole global batch one sequence
at a time, accumulating the loss over sequences, so that one sequence's
logits are all that is live.  Blocks, heads and vocabulary chunks are
recomputed in the backward pass (``jax.checkpoint``) to fit one chip; that
changes where the work happens, not the mathematics.

Every matrix product goes through one ``mm``: ``fp32_mm`` (float32 at
``Precision.HIGHEST``) for the reference, ``int8_mm`` (both operands of
every forward and backward product quantized to symmetric int8) for its
control.  Parameters and the optimizer state are dict trees in the
program's layout: ``{"params", "m", "v", "step"}``, the decoder blocks
stacked on a leading layer axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense

#: tokens of one vocabulary chunk of the loss (its logits are what is live)
LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# Matrix products: the reference and its control
# ---------------------------------------------------------------------------

fp32_mm = dense.fp32_mm


@jax.custom_vjp
def int8_mm(a, b):
    """The control: a [m, k] @ b [k, n] with both operands quantized to
    int8 (``dense.int8_mm``), and so are both products of its gradient."""
    return dense.int8_mm(a, b)


def _int8_fwd(a, b):
    return dense.int8_mm(a, b), (a, b)


def _int8_bwd(res, g):
    a, b = res
    return dense.int8_mm(g, b.T), dense.int8_mm(a.T, g)


int8_mm.defvjp(_int8_fwd, _int8_bwd)


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------

def dims(m: dict) -> dict:
    """``dense.dims`` plus the rotary width (``rot``) of each head."""
    k = dense.dims(m)
    k["rot"] = int(k["hd"] * m.get("partial_rotary_factor", 1.0))
    return k


def live_entries(m: dict) -> dict:
    """Masks over the last axis of the leaves (by key path) of which only
    some entries reach the loss: the key bias's dims past the rotary ones
    add the same amount to every score of a query, which softmax ignores.
    Their gradient is zero, so what Adam does with them is rounding noise
    in any implementation, and a comparison leaves them out."""
    k = dims(m)
    head = np.arange(k["hd"]) < k["rot"]
    return {("layers", "attn", "bk"): np.tile(head, k["KV"])}


# ---------------------------------------------------------------------------
# Forward and loss of one sequence
# ---------------------------------------------------------------------------

def _rope(x, theta, rot):
    """Rotary embedding on the first ``rot`` dims of each head (halves
    rotated against each other at the frequencies of a ``rot``-wide
    head); the rest pass through.  x [S, n, hd]."""
    return jnp.concatenate([dense._rope(x[..., :rot], theta), x[..., rot:]],
                           -1)


def _attention(k, p, x, mm):
    S = x.shape[0]
    H, KV, hd = k["H"], k["KV"], k["hd"]
    q = mm(x, p["wq"]) + p["bq"]
    kk = mm(x, p["wk"]) + p["bk"]
    v = (mm(x, p["wv"]) + p["bv"]).reshape(S, KV, hd)
    q = _rope(q.reshape(S, H, hd), k["theta"], k["rot"])
    kk = _rope(kk.reshape(S, KV, hd), k["theta"], k["rot"])
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = H // KV

    @jax.checkpoint
    def head(i):
        # one query head against its key/value head: scores [S, S]
        qi = jax.lax.dynamic_index_in_dim(q, i, 1, keepdims=False)
        ki = jax.lax.dynamic_index_in_dim(kk, i // group, 1, keepdims=False)
        vi = jax.lax.dynamic_index_in_dim(v, i // group, 1, keepdims=False)
        s = mm(qi, ki.T) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vi)

    out = jax.lax.map(head, jnp.arange(H))   # [H, S, hd]
    return mm(out.transpose(1, 0, 2).reshape(S, H * hd), p["wo"])


def _block(k, p, x, mm):
    x = x + _attention(k, p["attn"], dense._norm(k, p["ln1"], x), mm)
    h = dense._norm(k, p["ln2"], x)
    f = p["ff"]
    return x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                  f["w_down"])


def hidden(k, params, tokens, mm):
    """tokens [S] -> final-normed hidden states [S, D]."""
    x = params["embed"][tokens]

    def body(x, lp):
        return jax.checkpoint(functools.partial(_block, k, mm=mm))(lp, x), \
            None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return dense._norm(k, params["final_norm"], x)


def logits(m: dict, params, tokens, mm=fp32_mm):
    """Logits [S, V] of one sequence ``tokens`` [S]."""
    with jax.default_matmul_precision("highest"):
        return mm(hidden(dims(m), params, tokens, mm), params["lm_head"])


def sequence_loss(k, params, tokens, labels, mm):
    """Mean next-token cross-entropy of one sequence: tokens, labels [S]."""
    h = hidden(k, params, tokens, mm)
    S = h.shape[0]
    n = -(-S // LOSS_CHUNK)
    pad = n * LOSS_CHUNK - S
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, LOSS_CHUNK, -1)
    lab = jnp.pad(labels, (0, pad)).reshape(n, LOSS_CHUNK)
    real = (jnp.arange(n * LOSS_CHUNK) < S).reshape(n, LOSS_CHUNK)

    @jax.checkpoint
    def chunk(total, c):
        hc, lc, rc = c
        logits = mm(hc, params["lm_head"])
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lc[:, None], -1)[:, 0]
        return total + jnp.sum(jnp.where(rc, nll, 0.0)), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (h, lab, real))
    return total / S


def batch_loss(k, params, tokens, labels, weights, mm):
    """The global batch's loss, one sequence after another: the mean over
    the sequences of weight 1 (weights [B] of 0 or 1; the program's global
    batch is all ones, a control may leave sequences out)."""
    def body(total, seq):
        t, l, w = seq
        return total + w * jax.checkpoint(
            lambda t, l: sequence_loss(k, params, t, l, mm))(t, l), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (tokens, labels, weights))
    return total / jnp.sum(weights)


# ---------------------------------------------------------------------------
# Adam
# ---------------------------------------------------------------------------

def adam(opt: dict, state: dict, grads):
    """Clip the gradient to ``grad_clip`` by its global norm, then one Adam
    step (bias-corrected, eps outside the square root)."""
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    if opt.get("grad_clip"):
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    t = state["step"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * (m / c1) / (jnp.sqrt(v / c2)
                                                     + opt["eps"]),
        state["params"], m, v)
    return {"params": params, "m": m, "v": v, "step": t}


def step_fns(m: dict, opt: dict, mm=fp32_mm):
    """Jitted ``grad(params, tokens, labels, weights) -> (loss, grads)``
    and ``update(state, grads) -> state`` (the state donated): apart, the
    gradient's working memory and Adam's are never needed at once."""
    k = dims(m)

    def grad(params, tokens, labels, weights):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: batch_loss(k, p, tokens, labels, weights, mm))(
                params)

    return (jax.jit(grad),
            jax.jit(functools.partial(adam, opt), donate_argnums=(0,)))


def train(m: dict, opt: dict, state: dict, batches, weights=None,
          mm=fp32_mm, device=None):
    """Run one step per batch ((tokens, labels) [B, S] each) from the host
    ``state`` on one ``device``; returns (host params, host first moment,
    losses)."""
    device = device or jax.devices()[0]
    grad, update = step_fns(m, opt, mm)
    state = jax.device_put(state, device)
    losses = []
    for tokens, labels in batches:
        w = np.ones(len(tokens), np.float32) if weights is None else weights
        loss, grads = grad(state["params"], jnp.asarray(tokens, device=device),
                           jnp.asarray(labels, device=device),
                           jnp.asarray(w, device=device))
        state = update(state, grads)
        del grads
        losses.append(loss)
    losses = [float(x) for x in losses]
    params, first = jax.device_get((state["params"], state["m"]))
    return params, first, losses
