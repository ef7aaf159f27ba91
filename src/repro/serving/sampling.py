"""Per-request sampling: params, packing, and the fused device sampler.

``SamplingParams`` travels with a request from ``engine.submit()`` /
``Session.generate`` down into the engine's fused decode scan.  The
sampler is **counter-based**: the token at absolute sequence index
``i`` (0-based over prompt + generated) is drawn with
``fold_in(PRNGKey(seed), i)``, so a request's tokens are a pure
function of (prompt, params) — independent of batch composition, slot
assignment, paged vs slotted layout, mesh, warm vs cold caches, and
pipeline depth.  No RNG state is carried between steps and no host
sync is needed to advance it.

``temperature == 0`` lowers to argmax inside the same sampler, so
greedy requests in a mixed batch emit exactly the dedicated greedy
scan's tokens (the engine still dispatches the argmax-only scan when
*every* row is greedy, keeping the zero-dispatch next-token memo and
compile behavior of greedy traffic untouched).

This module is import-light (numpy/jax only) so ``scheduler.Request``
can carry a ``SamplingParams`` without layering cycles.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SamplingParams", "GREEDY", "pack_params", "sample_tokens",
           "PACKED_WIDTH"]

# Packed on-device layout, one int32 row per slot:
#   [bitcast(f32 temperature), bitcast(f32 top_p), top_k, seed]
PACKED_WIDTH = 4


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request generation parameters, validated at construction.

    temperature: 0 disables sampling (greedy argmax); > 0 scales logits.
    top_k: keep the k highest logits (0 disables the filter).
    top_p: nucleus filter — keep the smallest prob mass >= top_p
        (1.0 disables the filter).
    seed: per-request PRNG seed; the only source of randomness.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        t = self.temperature
        if not isinstance(t, (int, float)) or isinstance(t, bool) or t < 0:
            raise ValueError(
                f"temperature={t!r} must be a float >= 0")
        k = self.top_k
        if not isinstance(k, (int, np.integer)) or isinstance(k, bool) or k < 0:
            raise ValueError(f"top_k={k!r} must be an int >= 0")
        p = self.top_p
        if not isinstance(p, (int, float)) or isinstance(p, bool) \
                or not 0.0 < p <= 1.0:
            raise ValueError(f"top_p={p!r} must be a float in (0, 1]")
        s = self.seed
        if not isinstance(s, (int, np.integer)) or isinstance(s, bool) \
                or not 0 <= s < 2 ** 31:
            raise ValueError(f"seed={s!r} must be an int in [0, 2**31)")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


GREEDY = SamplingParams()


def pack_params(p: SamplingParams) -> np.ndarray:
    """One [PACKED_WIDTH] int32 row; floats travel bit-exact via bitcast."""
    return np.array([
        np.float32(p.temperature).view(np.int32),
        np.float32(p.top_p).view(np.int32),
        np.int32(p.top_k),
        np.int32(p.seed),
    ], dtype=np.int32)


def _top_threshold(key, target, weight=None):
    """Per row, the largest uint32 ``t`` whose tail — the sum of
    ``weight`` (1 where None) over the row's ``key >= t`` — still reaches
    ``target``; 0 when not even the whole row does.

    The tail only shrinks as ``t`` rises, so fixing ``t``'s bits from the
    top down, each kept iff the tail still reaches the target, is an exact
    bisection: 32 masked reductions over the row and no sort.
    """
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        hit = key >= cand[:, None]
        tail = jnp.sum(hit if weight is None else jnp.where(hit, weight, 0),
                       axis=-1)
        return jnp.where(tail >= target, cand, t)
    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(key.shape[:-1], jnp.uint32))


def _top_k_filter(scaled, top_k):
    """Keep each row's ``top_k`` largest values (0 keeps all); ties with
    the k-th survive.  The k-th is found on an order-preserving uint32
    key of the f32 value (negatives and -inf included), then compared as
    a float so that -0.0 and 0.0 tie as they do in a sort."""
    bits = jax.lax.bitcast_convert_type(scaled, jnp.uint32)
    neg = (bits >> 31) == 1
    key = jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))
    k = jnp.clip(top_k, 0, scaled.shape[-1])
    t = _top_threshold(key, k)
    kth = jax.lax.bitcast_convert_type(
        jnp.where(t >> 31 == 1, t & jnp.uint32(0x7fffffff), ~t), jnp.float32)
    return jnp.where((scaled >= kth[:, None]) | (k <= 0)[:, None],
                     scaled, -jnp.inf)


def _top_p_filter(scaled, top_p):
    """Nucleus filter: a token of probability v stays iff the mass of the
    tokens more probable than v is below ``top_p`` (so the most probable
    always stays, and ties stay or go together); ``top_p >= 1`` keeps
    every token.  Probabilities are >= 0, so their bit patterns order as
    they do: the smallest kept pattern is the largest ``t`` whose tail
    ``bits >= t`` still holds ``top_p`` of the mass."""
    probs = jax.nn.softmax(scaled, axis=-1)
    bits = jax.lax.bitcast_convert_type(probs, jnp.uint32)
    t = _top_threshold(bits, top_p, weight=probs)
    keep = (bits >= t[:, None]) | (top_p >= 1.0)[:, None]
    return jnp.where(keep, scaled, -jnp.inf)


@jax.named_scope("sampler")
def sample_tokens(logits, packed, idx):
    """Sample one token per row.  Pure function of (logits, packed, idx).

    logits [S, V] — next-token logits per row.
    packed [S, PACKED_WIDTH] int32 — per-row packed SamplingParams.
    idx    [S] int32 — absolute sequence index of the token being drawn
        (counter folded into the row's seed).

    Returns [S] int32 tokens.  Rows with temperature == 0 take the
    argmax path (bit-identical to the greedy scan); the filters follow
    the usual order: temperature scale -> top-k -> top-p -> categorical.
    Each filter finds its cutoff by an exact per-row threshold search
    (``_top_threshold``), not a vocabulary sort, and runs only when some
    row asks for it.  The draw (a categorical over the kept logits,
    per-row threefry) still costs passes over every row, so the whole
    pipeline sits behind a ``lax.cond``: an all-greedy call — every
    speculative verify of greedy traffic — pays for the argmax only.
    """
    logits = logits.astype(jnp.float32)
    temp = jax.lax.bitcast_convert_type(packed[:, 0], jnp.float32)
    top_p = jax.lax.bitcast_convert_type(packed[:, 1], jnp.float32)
    top_k = packed[:, 2]
    seed = packed[:, 3]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_rows(_):
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        scaled = jax.lax.cond(jnp.any(top_k > 0), _top_k_filter,
                              lambda s, _: s, scaled, top_k)
        # top-p over the post-k distribution
        scaled = jax.lax.cond(jnp.any(top_p < 1.0), _top_p_filter,
                              lambda s, _: s, scaled, top_p)

        def draw(seed_i, idx_i, row):
            key = jax.random.fold_in(jax.random.PRNGKey(seed_i), idx_i)
            return jax.random.categorical(key, row)

        sampled = jax.vmap(draw)(seed, idx.astype(jnp.int32), scaled)
        return jnp.where(temp > 0.0, sampled.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(temp > 0.0), sampled_rows,
                        lambda _: greedy, operand=None)
