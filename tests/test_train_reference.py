"""stablelm-2 against its plain fp32 reference (``bench/reference/train.py``,
which imports nothing of the program): the data-parallel trainer on four
replicas against the sequential reference for three Adam steps, the
forward logits with partial rotary and QKV bias, and a paged serve.

The smoke config computes in float32 on the CPU, so the program and the
reference differ by summation order alone; each tolerance below says how
much room that leaves.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.drivers import train_dp  # noqa: E402
from bench.reference import dense  # noqa: E402
from bench.reference import train as ref  # noqa: E402
from repro import api  # noqa: E402
from repro.configs import ServeConfig, get_config  # noqa: E402
from repro.models import common, registry, transformer  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

#: the smoke config as the reference reads it (hf config.json keys)
MODEL = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128,
         "vocab_size": 256, "partial_rotary_factor": 0.25,
         "rope_theta": 10000.0, "layer_norm_eps": 1e-05,
         "use_qkv_bias": True, "tie_word_embeddings": False}
CFG = {"model": MODEL,
       "load": {"arch": "stablelm-1.6b", "smoke": True, "mesh": "4x1",
                "dp_mode": "replicated", "allreduce": "layerwise",
                "overrides": {"attn_remat": True}},
       "train": {"optimizer": {"name": "adam", "lr": 1e-3, "beta1": 0.9,
                               "beta2": 0.95, "eps": 1e-8,
                               "grad_clip": 1.0}}}
MIX = {"seq_len": 32, "global_batch": 4, "checked_steps": 3}
#: float32 on both sides, summed in other orders:
#: - the loss is a mean of O(5) values, rounding ~1e-7 relative; 1e-5;
#: - the first moment is a sum of gradients, ~1e-6 relative; 1e-4;
#: - Adam divides by sqrt(v), so entries of tiny gradient turn rounding
#:   into update; ~1e-5 relative at worst; 1e-3
TOL = {"loss_gap": 1e-5, "moment_gap": 1e-4, "update_gap": 1e-3}


def _dp4_readings(seed=3):
    """Three steps from the reference's weights and zero moments."""
    before = train_dp.initial_state(MODEL, seed)
    session = train_dp.build(CFG, seed, before["params"])
    batches = train_dp.checked_batches(MIX, MODEL["vocab_size"], seed)
    kw = train_dp.train_kwargs(CFG, MIX)
    p_after, m_after, losses = train_dp.checked_steps(session, batches, kw)
    want = ref.train(MODEL, CFG["train"]["optimizer"], before, batches)
    return train_dp.readings((p_after, m_after, losses), want,
                             before["params"], ref.live_entries(MODEL))


def test_dp4_trainer_matches_sequential_reference():
    got = _dp4_readings()
    assert all(got[k] <= TOL[k] for k in TOL), got


def test_dp4_trainer_without_the_exchange_fails(monkeypatch):
    """Each replica steps on its own shard's gradient: the replicas'
    state (rank 0's, as the host reads it) leaves the reference's."""
    from repro.core import allreduce

    def no_exchange(grads, strategy, axes, bucket_bytes=0, err=None):
        return jax.tree.map(lambda g: g.astype(jnp.float32), grads), err

    monkeypatch.setattr(allreduce, "reduce_gradients", no_exchange)
    got = _dp4_readings()
    assert not all(got[k] <= TOL[k] for k in TOL), got
    assert got["moment_gap"] > 100 * TOL["moment_gap"], got


def test_load_params_are_where_training_starts():
    """Weights given to ``api.load`` are what serving sees and what every
    replica of the DP-4 trainer holds after its broadcast init."""
    params = dense.make_params(MODEL, 5)
    session = train_dp.build(CFG, 5, params)
    for a, b in zip(jax.tree.leaves(session.params), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    session.train(steps=1, seq_len=8, global_batch=4, lr=0.0)
    state = session.train_state
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params)):
        assert a.sharding.is_fully_replicated
        for shard in a.addressable_shards:
            assert np.array_equal(np.asarray(shard.data), np.asarray(b))


def _smoke(**kw):
    return get_config("stablelm-1.6b", smoke=True).replace(**kw)


def _params(seed=0):
    """The reference's seeded weights (biases and LayerNorm gains spread
    away from their neutral values), in the program's tree layout."""
    params = dense.make_params(MODEL, seed)
    specs = registry.build(_smoke()).specs
    assert jax.tree.structure(params) == jax.tree.structure(
        common.tree_map_specs(lambda s: 0, specs))
    return params


def _program_logits(cfg, params, tokens):
    x, _, _ = transformer.lm_forward(cfg, params, jnp.asarray(tokens)[None])
    return np.asarray(transformer.lm_head(cfg, params, x)[0])


#: fp32 forward on both sides; the program's attention sums in blocks with
#: an online softmax, the reference row by row: ~1e-6 of the logits' scale
LOGIT_TOL = 1e-4


def test_forward_logits_match_reference():
    params = _params()
    tokens = np.random.default_rng(1).integers(0, 256, 48).astype(np.int32)
    want = np.asarray(ref.logits(MODEL, params, jnp.asarray(tokens)))
    scale = np.abs(want).max()
    got = _program_logits(_smoke(), params, tokens)
    assert np.abs(got - want).max() <= LOGIT_TOL * scale
    # the test sees both mechanisms: rotating every dim, or dropping the
    # biases, moves the logits far past the tolerance
    full = _program_logits(_smoke(rotary_fraction=1.0), params, tokens)
    nobias = _program_logits(_smoke(qkv_bias=False), params, tokens)
    assert np.abs(full - want).max() > 100 * LOGIT_TOL * scale
    assert np.abs(nobias - want).max() > 100 * LOGIT_TOL * scale


def _rope_before_fraction(x, positions, theta):
    """apply_rope as it was before the rotary fraction (the full-rotary
    path must still trace to exactly this)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(common.rope_frequencies(hd, theta))
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_full_rotary_unchanged_bit_for_bit(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 4, 64), dtype)
    pos = jnp.arange(24, dtype=jnp.int32)
    old = _rope_before_fraction(x, pos, 1e4)
    for rot in (None, 64):
        new = common.apply_rope(x, pos, 1e4, rot)
        assert np.array_equal(np.asarray(new), np.asarray(old))
        assert str(jax.make_jaxpr(common.apply_rope, static_argnums=(2, 3))(
            x, pos, 1e4, rot)) == str(jax.make_jaxpr(
                _rope_before_fraction, static_argnums=(2,))(x, pos, 1e4))


def test_partial_rotary_passes_the_rest_through():
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 4, 64))
    pos = jnp.arange(24, dtype=jnp.int32)
    out = common.apply_rope(x, pos, 1e4, 16)
    assert np.array_equal(np.asarray(out[..., 16:]), np.asarray(x[..., 16:]))
    assert np.array_equal(np.asarray(out[..., :16]), np.asarray(
        _rope_before_fraction(x[..., :16], pos, 1e4)))


def test_paged_serve_matches_reference_logits():
    """Paged prefill then decode through the engine: each greedy token is
    the reference's best at its position, within the logit tolerance, so
    the serve paths' rotary and biases are the reference's."""
    params = _params(2)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).tolist() for n in (7, 19, 12)]
    eng = ServingEngine(_smoke(), ServeConfig(max_batch=2, max_seq_len=64,
                                              page_size=8, decode_steps=2),
                        params=params)
    assert eng.pool.__class__.__name__ == "PagedKVCachePool"
    outs = eng.generate(prompts, 10)
    for prompt, out in zip(prompts, outs):
        seq = jnp.asarray(prompt + out, jnp.int32)
        logits = np.asarray(ref.logits(MODEL, params, seq))
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gap = rows.max(-1) - rows[np.arange(len(out)), out]
        assert gap.max() <= LOGIT_TOL * np.abs(logits).max(), gap
