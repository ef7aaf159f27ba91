"""Plain float32 reference of a dense decoder-only transformer.

This is the benchmark's yardstick for ``correct``: it imports nothing of the
program under test.  It follows the published architecture of the dense
configurations (pre-norm decoder blocks, grouped-query attention with
rotary position embedding, SwiGLU feed-forward, untied LM head), with the
departures that each configuration file lists under ``departures``.

Every matrix product goes through one ``mm`` function, so the same code is
the reference (``fp32_mm``: float32 at ``Precision.HIGHEST``) and its
control (``int8_mm``: symmetric int8, one precision below the bfloat16
compute that the configurations state).

Parameters are a dict tree laid out as the program takes them (one leaf
per weight, the decoder blocks stacked on a leading layer axis).  The
benchmark makes them itself from the seed (``make_params``) and hands the
same tree to the program and to this reference.

RMSNorm's gain is stored as an offset from one (``x_hat * (1 + scale)``),
which is the program's parameter convention; LayerNorm stores gain and
bias as they are.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Matrix products: the reference and its control
# ---------------------------------------------------------------------------

def fp32_mm(a, b):
    """a [..., k] @ b [k, n] in float32 at full precision."""
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127), s


def int8_mm(a, b):
    """The control: both operands of a 2-D product quantized to symmetric
    int8 (per row of ``a``, per column of ``b``), products summed in
    float32."""
    qa, sa = _q8(a.astype(jnp.float32), -1)
    qb, sb = _q8(b.astype(jnp.float32), -2)
    return jnp.matmul(qa, qb, precision=HIGHEST) * sa * sb


# ---------------------------------------------------------------------------
# Shapes and weights
# ---------------------------------------------------------------------------

def dims(m: dict) -> dict:
    """The sizes the reference needs, from a configuration's ``model``
    block (keys as in the published ``config.json``)."""
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    eps_key = "rms_norm_eps" if "rms_norm_eps" in m else "layer_norm_eps"
    return {
        "D": d, "H": h, "KV": m["num_key_value_heads"],
        "hd": m.get("head_dim") or d // h, "F": m["intermediate_size"],
        "V": m["vocab_size"], "L": m["num_hidden_layers"],
        "theta": float(m["rope_theta"]),
        "norm": "rms" if eps_key == "rms_norm_eps" else "layer",
        "eps": float(m[eps_key]),
        "bias": bool(m.get("use_qkv_bias") or m.get("attention_bias")),
    }


def param_shapes(m: dict) -> dict:
    """Leaf shapes, in the tree layout the parameters are made in."""
    k = dims(m)
    D, H, KV, hd, F, V, L = (k[x] for x in ("D", "H", "KV", "hd", "F", "V",
                                            "L"))
    norm = {"scale": (D,)} if k["norm"] == "rms" else \
        {"scale": (D,), "bias": (D,)}
    attn = {"wq": (D, H * hd), "wk": (D, KV * hd), "wv": (D, KV * hd),
            "wo": (H * hd, D)}
    if k["bias"]:
        attn.update(bq=(H * hd,), bk=(KV * hd,), bv=(KV * hd,))
    layer = {"ln1": norm, "attn": attn, "ln2": dict(norm),
             "ff": {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}}
    stacked = jax.tree.map(lambda s: (L,) + s, layer,
                           is_leaf=lambda x: isinstance(x, tuple))
    return {"embed": (V, D), "layers": stacked, "final_norm": dict(norm),
            "lm_head": (D, V)}


def _leaf_init(path: str, shape, key, norm: str):
    """Seeded values for one leaf: fan-in scaled normal matrices, unit
    normal embeddings, norm gains and biases spread around their neutral
    value so that a wrong convention shows."""
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return z
    if name in ("bq", "bk", "bv") or (name == "bias"):
        return 0.1 * z
    if name == "scale":
        return 0.1 * z if norm == "rms" else 1.0 + 0.1 * z
    return z / math.sqrt(shape[-2])


def seed_key(seed: int):
    """A PRNG key from any whole seed up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_fn(m: dict):
    """A function of a PRNG key that makes every weight, in float32."""
    shapes = param_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(p, "key", p)) for p in path)
             for path, _ in flat]
    norm = dims(m)["norm"]

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf_init(p, s, k, norm)
                  for p, (_, s), k in zip(paths, flat, keys)]
        return jax.tree.unflatten(treedef, leaves)

    return build


def make_params(m: dict, seed: int):
    """Every weight from ``seed``, in one jitted call on the device."""
    return jax.jit(init_fn(m))(seed_key(seed))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _norm(k, p, x):
    if k["norm"] == "rms":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + k["eps"])
        return x * (1.0 + p["scale"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + k["eps"]) * p["scale"] + p["bias"]


def _rope(x, theta):
    """Rotary embedding, halves rotated against each other.  x [S, n, hd]."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(k, p, x, mm):
    S = x.shape[0]
    H, KV, hd = k["H"], k["KV"], k["hd"]
    q, kk, v = mm(x, p["wq"]), mm(x, p["wk"]), mm(x, p["wv"])
    if k["bias"]:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q = _rope(q.reshape(S, H, hd), k["theta"])
    kk = _rope(kk.reshape(S, KV, hd), k["theta"])
    v = v.reshape(S, KV, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = H // KV

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
    x = x + _attention(k, p["attn"], _norm(k, p["ln1"], x), mm)
    h = _norm(k, p["ln2"], x)
    f = p["ff"]
    return x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                  f["w_down"])


def hidden(k, params, tokens, mm):
    """tokens [S] -> final-normed hidden states [S, D]."""
    x = params["embed"][tokens]

    def body(x, lp):
        return _block(k, lp, x, mm), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _norm(k, params["final_norm"], x)


# ---------------------------------------------------------------------------
# Serving: logits at the served positions
# ---------------------------------------------------------------------------

def served_logits(m: dict, params, tokens, rows, mm=fp32_mm):
    """Logits [len(rows), V] at positions ``rows`` of one sequence
    ``tokens`` [S] (prompt followed by the served tokens, padded at the
    end; causality keeps the padding out of every earlier row)."""
    k = dims(m)
    h = hidden(k, params, tokens, mm)[rows]
    return mm(h, params["lm_head"])


def served_gap(m: dict, params, tokens, rows, served, control=False):
    """For each row: the reference's best logit less its logit of the
    served token, and (with ``control``) less its logit of the token that
    the int8 control puts first.  ``served`` [len(rows)] holds the token
    served at row + 1; rows past a request's end repeat its last."""
    ref = served_logits(m, params, tokens, rows)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return gap, None
    ctrl = served_logits(m, params, tokens, rows, int8_mm)
    pick = jnp.argmax(ctrl, -1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
