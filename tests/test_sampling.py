"""Per-request sampling + speculative decoding (serving/sampling.py,
serving/spec.py, engine integration).

The product guarantee under test: a request's tokens are a pure function
of (prompt, SamplingParams) — the counter-based PRNG keys every draw by
(request seed, absolute token index), so batch composition, slot
assignment, preemption/resume, paged vs slotted layout, a 2x2 mesh, warm
vs cold prefix caches and the pipeline depth must all be invisible in the
output.  Speculative decoding rides the same guarantee: verification
deterministically replays the engine's own sampler at each drafted
position, so spec-on output is token-identical to spec-off, greedy and
sampled alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MeshConfig, ServeConfig, get_config
from repro.serving import GREEDY, SamplingParams, ServingEngine
from repro.serving.sampling import (_top_k_filter, _top_p_filter,
                                    pack_params, sample_tokens)
from repro.serving.spec import NGramDrafter

ARCHS = {
    "full": ("qwen2.5-14b", {}),
    "mla": ("deepseek-v2-lite-16b", {}),
    "ring": ("mixtral-8x22b", {}),
}

#: one non-trivial sampled config reused across the matrix
SAMPLED = SamplingParams(temperature=0.8, top_k=8, top_p=0.9, seed=13)


def _cfg(kind):
    arch, overrides = ARCHS[kind]
    cfg = get_config(arch, smoke=True)
    return cfg.replace(**overrides) if overrides else cfg


def _prompts(rng, vocab, lengths):
    return [list(rng.integers(0, vocab, (l,))) for l in lengths]


def _rep_prompt(vocab, n=12, a=7, b=3):
    """A repetitive prompt the n-gram drafter can always propose from."""
    return ([a % vocab, b % vocab] * n)[:n]


def _engine(cfg, depth=1, params=None, mesh_cfg=None, **kw):
    base = dict(max_batch=2, max_seq_len=40, max_new_tokens=5,
                decode_steps=2, kv_layout="paged",
                page_size=8 if cfg.attn_kind == "mla" else 4,
                pipeline_depth=depth)
    base.update(kw)
    return ServingEngine(cfg, ServeConfig(**base), params=params,
                         mesh_cfg=mesh_cfg)


# ---------------------------------------------------------------------------
# Config seams: SamplingParams + ServeConfig spec knobs validate loudly
# ---------------------------------------------------------------------------

def test_sampling_params_validate():
    p = SamplingParams(temperature=0.5, top_k=4, top_p=0.9, seed=7)
    assert not p.greedy and GREEDY.greedy
    assert SamplingParams(temperature=0.0).greedy
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    for bad_p in (0.0, 1.5, -0.2):
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=bad_p)
    for bad_s in (-1, 2 ** 31):
        with pytest.raises(ValueError, match="seed"):
            SamplingParams(seed=bad_s)


def test_serve_config_spec_knobs_validate():
    ServeConfig(spec_tokens=8, enable_spec=False).validate()
    with pytest.raises(ValueError, match="spec_tokens"):
        ServeConfig(spec_tokens=0).validate()
    with pytest.raises(ValueError, match="enable_spec"):
        ServeConfig(enable_spec="yes").validate()


def test_engine_rejects_non_sampling_params():
    eng = _engine(_cfg("full"))
    with pytest.raises(TypeError, match="sampling"):
        eng.submit([1, 2, 3], sampling={"temperature": 1.0})


# ---------------------------------------------------------------------------
# The sampler itself (pure device function)
# ---------------------------------------------------------------------------

def test_sample_tokens_greedy_and_determinism():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 32)).astype(np.float32)
    idx = np.arange(4, dtype=np.int32) + 10
    packed = np.stack([pack_params(GREEDY)] * 4)
    out = np.asarray(sample_tokens(logits, packed, idx))
    assert (out == logits.argmax(-1)).all()        # temp 0 -> argmax
    # top_k=1 pins the support to the argmax whatever the temperature
    packed1 = np.stack([pack_params(SamplingParams(
        temperature=2.0, top_k=1, seed=5))] * 4)
    out1 = np.asarray(sample_tokens(logits, packed1, idx))
    assert (out1 == logits.argmax(-1)).all()
    # pure function of (logits, params, idx): row position is irrelevant
    packed_s = np.stack([pack_params(SAMPLED)] * 4)
    a = np.asarray(sample_tokens(logits, packed_s, idx))
    b = np.asarray(sample_tokens(logits[::-1], packed_s[::-1], idx[::-1]))
    assert (a == b[::-1]).all()
    # ... but the counter index matters (different position, fresh draw)
    low_t = np.stack([pack_params(SamplingParams(
        temperature=5.0, seed=3))] * 4)
    flat = np.zeros((4, 32), np.float32)           # uniform -> index decides
    x = np.asarray(sample_tokens(flat, low_t, idx))
    y = np.asarray(sample_tokens(flat, low_t, idx + 17))
    assert (x != y).any()


@jax.jit
def _sort_reference(logits, packed, idx):
    """The sort-based sampler the threshold search replaced, kept verbatim
    as the reference: (filtered scaled logits, tokens)."""
    logits = logits.astype(jnp.float32)
    temp = jax.lax.bitcast_convert_type(packed[:, 0], jnp.float32)
    top_p = jax.lax.bitcast_convert_type(packed[:, 1], jnp.float32)
    top_k = packed[:, 2]
    seed = packed[:, 3]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    # top-k: threshold at the k-th largest value (ties past k survive
    # — deterministic either way, which is all reproducibility needs)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.clip(top_k, 0, V)
    kth = jnp.take_along_axis(desc, jnp.maximum(k - 1, 0)[:, None],
                              axis=-1)
    scaled = jnp.where((scaled >= kth) | (k <= 0)[:, None],
                       scaled, -jnp.inf)
    # top-p over the post-k distribution: keep the smallest prefix of
    # the sorted probs whose mass reaches top_p (the first always stays)
    probs = jax.nn.softmax(scaled, axis=-1)
    p_desc = jnp.sort(probs, axis=-1)[:, ::-1]
    cum = jnp.cumsum(p_desc, axis=-1)
    kept = (cum - p_desc) < top_p[:, None]
    cutoff = jnp.min(jnp.where(kept, p_desc, jnp.inf), axis=-1)
    keep = (probs >= cutoff[:, None]) | (top_p >= 1.0)[:, None]
    scaled = jnp.where(keep, scaled, -jnp.inf)

    def draw(seed_i, idx_i, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_i), idx_i)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seed, idx.astype(jnp.int32), scaled)
    return scaled, jnp.where(temp > 0.0, sampled.astype(jnp.int32), greedy)


@jax.jit
def _threshold_filters(logits, packed):
    """The new sampler's filters, exposed: (after top-k, after top-p)."""
    temp = jax.lax.bitcast_convert_type(packed[:, 0], jnp.float32)
    top_p = jax.lax.bitcast_convert_type(packed[:, 1], jnp.float32)
    after_k = _top_k_filter(logits / jnp.maximum(temp, 1e-6)[:, None],
                            packed[:, 2])
    return after_k, _top_p_filter(after_k, top_p)


_sample_jit = jax.jit(sample_tokens)

#: a token whose exact mass above it lies this close to top_p may fall on
#: either side of the nucleus edge: both samplers sum f32 probabilities,
#: in different orders
BOUNDARY_TOL = 1e-6

V_SMALL = 1000


def _sp(t, p=1.0, k=0):
    return (t, p, k)


# id -> (vocab, rows, logits kind, per-row (temperature, top_p, top_k)
# cycled over the rows); every row gets its own seed
EXACT_CASES = {
    **{f"t{t}-p{p}": (V_SMALL, 128, "normal", [_sp(t, p)])
       for t in (0.3, 0.8, 2.0) for p in (0.5, 0.9, 0.95, 1.0)},
    **{f"k{k}": (V_SMALL, 128, "normal", [_sp(0.8, 1.0, k)])
       for k in (1, 8, V_SMALL, V_SMALL + 5)},
    "k8-p0.9": (V_SMALL, 128, "normal", [_sp(0.8, 0.9, 8)]),
    "kV-p0.5": (V_SMALL, 128, "normal", [_sp(2.0, 0.5, V_SMALL)]),
    "k64-p0.95": (V_SMALL, 128, "normal", [_sp(0.3, 0.95, 64)]),
    "greedy-mixed": (V_SMALL, 128, "normal",
                     [_sp(0.0), _sp(0.8, 0.95), _sp(0.3, 1.0, 8),
                      _sp(2.0, 0.5, 1), _sp(0.8, 0.9, V_SMALL + 5)]),
    "ties": (V_SMALL, 128, "ties",
             [_sp(0.8, 0.9, 8), _sp(2.0, 0.5), _sp(0.3, 0.95, 3),
              _sp(0.8, 1.0, 1), _sp(0.0)]),
    "neg-inf": (V_SMALL, 128, "neg_inf",
                [_sp(0.8, 0.9), _sp(2.0, 1.0, 8), _sp(0.3, 0.5, 40),
                 _sp(0.8, 0.95, V_SMALL), _sp(0.0)]),
    # the nemo-chat cell's shape and mix: 32 slots of a 131072-token
    # vocabulary, T 0.8 and top_p 0.95, a greedy tenth
    "full-32x131072": (131072, 32, "normal",
                       [_sp(0.0)] + [_sp(0.8, 0.95)] * 9),
}


def _case_inputs(name):
    V, rows, kind, params = EXACT_CASES[name]
    rng = np.random.default_rng(sorted(EXACT_CASES).index(name))
    logits = rng.normal(size=(rows, V)).astype(np.float32)
    if kind == "ties":
        # half the rows flat, the rest on a coarse grid: long runs of
        # repeated values straddle every cutoff
        logits = np.round(logits * 2) / 2
        logits[::2] = 0.0
        logits[1::4] = np.round(logits[1::4])
    elif kind == "neg_inf":
        logits[rng.random(logits.shape) < 0.3] = -np.inf
        # rows with only a handful of finite entries, fewer than top_k
        logits[::3, 5:] = -np.inf
    packed = np.stack([
        pack_params(SamplingParams(temperature=t, top_p=p, top_k=k,
                                   seed=1000 + r))
        for r, (t, p, k) in zip(range(rows),
                                params * (rows // len(params) + 1))])
    idx = np.arange(rows, dtype=np.int32) + 100
    return logits, packed, idx


def _boundary_rows(probs, ref_keep, new_keep, top_p):
    """Rows whose keep masks differ, each checked to differ only at tokens
    whose exact mass above them lies within BOUNDARY_TOL of top_p."""
    rows = []
    for r in np.nonzero((ref_keep != new_keep).any(-1))[0]:
        p64 = probs[r].astype(np.float64)
        desc = np.sort(p64)[::-1]
        above = np.concatenate([[0.0], np.cumsum(desc)])
        for v in p64[ref_keep[r] != new_keep[r]]:
            mass = above[np.searchsorted(-desc, -v, side="left")]
            assert abs(mass - top_p[r]) <= BOUNDARY_TOL, (
                f"row {r}: token of prob {v} has mass {mass} above it, "
                f"not within {BOUNDARY_TOL} of top_p {top_p[r]}")
        rows.append(int(r))
    return rows


@pytest.mark.parametrize("case", list(EXACT_CASES))
def test_threshold_sampler_matches_sort_reference(case):
    """The threshold search keeps what the two vocabulary sorts kept and
    draws the same tokens: top-k exactly (integer counts), top-p up to f32
    rounding at the nucleus edge, reported and held under 1% of rows."""
    logits, packed, idx = _case_inputs(case)
    top_p = packed[:, 1].view(np.float32)
    ref_scaled, ref_tok = map(np.asarray,
                              _sort_reference(logits, packed, idx))
    after_k, new_scaled = map(np.asarray, _threshold_filters(logits, packed))
    # top-k alone: the reference with every top_p at 1 against the search
    no_p = packed.copy()
    no_p[:, 1] = np.float32(1.0).view(np.int32)
    ref_k = np.asarray(_sort_reference(logits, no_p, idx)[0])
    assert np.array_equal(ref_k == -np.inf, after_k == -np.inf)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(after_k), axis=-1))
    boundary = _boundary_rows(probs, ref_scaled != -np.inf,
                              new_scaled != -np.inf, top_p)
    print(f"{case}: boundary-rounding rows {boundary} of {len(logits)}")
    assert len(boundary) < 0.01 * len(logits), boundary
    tok = np.asarray(_sample_jit(logits, packed, idx))
    rest = np.setdiff1d(np.arange(len(logits)), boundary)
    assert np.array_equal(tok[rest], ref_tok[rest])


def test_ngram_drafter_proposes_continuations():
    d = NGramDrafter(ngram=2)
    hist = [1, 2, 3, 1, 2, 3, 1, 2]
    assert d.propose(hist, 3) == (3, 1, 2)         # replay after [1, 2]
    assert d.propose(hist + [9], 3) == ()          # unseen suffix [2, 9]
    assert d.propose(hist, 0) == ()


# ---------------------------------------------------------------------------
# Reproducibility matrix: same (prompt, params) -> same tokens, everywhere
# ---------------------------------------------------------------------------

def test_sampled_invariant_to_batch_composition():
    """The target request emits the same tokens served alone, batched with
    greedy neighbours, and batched with other sampled requests."""
    cfg = _cfg("full")
    rng = np.random.default_rng(3)
    target = _prompts(rng, cfg.vocab_size, [9])[0]
    others = _prompts(rng, cfg.vocab_size, [7, 12, 5])
    e = _engine(cfg, max_batch=2)
    alone = e.generate([target], 5, sampling=SAMPLED)[0]
    e2 = _engine(cfg, params=e.params, max_batch=2)
    mixed = e2.generate([others[0], target, others[1]], 5,
                        sampling=[None, SAMPLED, None])
    assert mixed[1] == alone
    e3 = _engine(cfg, params=e.params, max_batch=2)
    allsamp = e3.generate(
        [others[2], target], 5,
        sampling=[SamplingParams(temperature=1.3, seed=99), SAMPLED])
    assert allsamp[1] == alone


@pytest.mark.parametrize("kind", sorted(ARCHS))
def test_sampled_pipeline_depth_invariant(kind):
    cfg = _cfg(kind)
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, cfg.vocab_size, [7, 12, 5])
    samp = [SAMPLED, None, SamplingParams(temperature=1.1, top_p=0.8,
                                          seed=21)]
    e1 = _engine(cfg, depth=1)
    out1 = e1.generate(prompts, 5, sampling=samp)
    e2 = _engine(cfg, depth=2, params=e1.params)
    assert e2.generate(prompts, 5, sampling=samp) == out1


def test_sampled_paged_matches_slotted():
    cfg = _cfg("full")
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, cfg.vocab_size, [6, 11])
    e_paged = _engine(cfg)
    out_paged = e_paged.generate(prompts, 5, sampling=SAMPLED)
    e_slot = _engine(cfg, params=e_paged.params, kv_layout="slotted")
    assert e_slot.generate(prompts, 5, sampling=SAMPLED) == out_paged


def test_sampled_under_mesh_matches_single_device():
    cfg = _cfg("full")
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, cfg.vocab_size, [7, 11, 6, 9])
    mesh_cfg = MeshConfig(shape=(2, 2), axis_names=("data", "model"))
    e_mesh = _engine(cfg, depth=2, mesh_cfg=mesh_cfg, max_batch=4)
    out_mesh = e_mesh.generate(prompts, 4, sampling=SAMPLED)
    e_one = _engine(cfg, params=e_mesh.params, max_batch=4)
    assert e_one.generate(prompts, 4, sampling=SAMPLED) == out_mesh


def test_sampled_warm_vs_cold_prefix_cache():
    """A warm prefix cache changes how much prefill runs, never which
    tokens come out; the greedy next-token memo must not serve a sampled
    request's first token."""
    cfg = _cfg("full")
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, cfg.vocab_size, [12, 12])
    prompts[1] = list(prompts[0])                  # identical prompts
    eng = _engine(cfg, depth=2, enable_prefix_cache=True)
    # greedy pass seeds the prefix cache AND the next-token memo
    greedy_out = eng.generate(prompts, 5)
    assert greedy_out[0] == greedy_out[1]
    eng.metrics.reset()
    eng.results.clear()
    cold = _engine(cfg, params=eng.params,
                   enable_prefix_cache=False).generate(
        [prompts[0]], 5, sampling=SAMPLED)[0]
    warm = eng.generate(prompts, 5, sampling=SAMPLED)
    assert warm[0] == warm[1] == cold
    assert eng.metrics.prefix_hit_tokens > 0       # pages were shared
    assert cold != greedy_out[0]                   # sampling actually sampled


def test_sampled_preemption_resume_exact():
    """Page pressure evicts a sampled request mid-run; on resume it lands
    in a different slot with a longer prompt — the counter-keyed PRNG
    must replay the identical continuation."""
    cfg = _cfg("full")
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, cfg.vocab_size, [14, 15])
    kw = dict(max_seq_len=32, max_new_tokens=12)
    e_calm = _engine(cfg, **kw)
    out_calm = e_calm.generate(prompts, 12, sampling=SAMPLED)
    e_tight = _engine(cfg, depth=2, params=e_calm.params, num_pages=12, **kw)
    out_tight = e_tight.generate(prompts, 12, sampling=SAMPLED)
    assert e_tight.metrics.preemptions >= 1
    assert out_tight == out_calm


def test_greedy_params_identical_to_default_path():
    """temperature=0 through the sampling machinery is byte-identical to
    the plain greedy engine — including in a mixed batch, where greedy
    rows ride the sampled scan."""
    cfg = _cfg("full")
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, cfg.vocab_size, [7, 12])
    e = _engine(cfg)
    plain = e.generate(prompts, 5)
    e2 = _engine(cfg, params=e.params)
    explicit = e2.generate(prompts, 5, sampling=SamplingParams())
    assert explicit == plain
    e3 = _engine(cfg, params=e.params, max_batch=2)
    mixed = e3.generate([prompts[0], prompts[1]], 5,
                        sampling=[None, SAMPLED])
    assert mixed[0] == plain[0]


def test_sampler_dispatch_counters():
    """sampler.sampled_dispatches counts the decode dispatches that take
    the sampled scan, sampler.topk_dispatches those in which some row's
    top_k > 0 runs the top-k search; greedy traffic leaves both at 0."""
    cfg = _cfg("full")
    rng = np.random.default_rng(9)
    prompts = _prompts(rng, cfg.vocab_size, [7, 12])

    def counts(sampling):
        eng = _engine(cfg, trace=True, enable_spec=False)
        eng.generate(prompts, 5, sampling=sampling)
        evs = eng.tracer.events
        last = {e[1]: e[4] for e in evs if e[0] == "C"
                and e[1].startswith("sampler.")}
        sampled = sum(1 for e in evs if e[0] == "X"
                      and e[1] == "decode.dispatch" and e[5]["sampled"])
        return last, sampled

    last, sampled = counts(None)
    assert last == {"sampler.sampled_dispatches": 0,
                    "sampler.topk_dispatches": 0} and sampled == 0
    last, sampled = counts([SamplingParams(temperature=0.8, top_p=0.9,
                                           seed=1), None])
    assert sampled > 0
    assert last == {"sampler.sampled_dispatches": sampled,
                    "sampler.topk_dispatches": 0}
    last, sampled = counts([SamplingParams(temperature=0.8, top_k=8,
                                           seed=1), None])
    assert sampled > 0
    assert last == {"sampler.sampled_dispatches": sampled,
                    "sampler.topk_dispatches": sampled}


# ---------------------------------------------------------------------------
# Speculative decoding: spec-on == spec-off, tokens and pool hygiene
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(ARCHS))
def test_spec_identity_greedy(kind):
    """Repetitive prompts make the drafter propose every cycle; accepted
    or rejected, the output must match the spec-off engine exactly."""
    cfg = _cfg(kind)
    rng = np.random.default_rng(2)
    prompts = [_rep_prompt(cfg.vocab_size, a=1, b=1),
               _prompts(rng, cfg.vocab_size, [9])[0],
               _rep_prompt(cfg.vocab_size, n=16, a=1, b=1)]
    kw = dict(max_seq_len=48, max_new_tokens=16, spec_tokens=4)
    e_off = _engine(cfg, enable_spec=False, **kw)
    out_off = e_off.generate(prompts, 16)
    e_on = _engine(cfg, params=e_off.params, enable_spec=True, **kw)
    out_on = e_on.generate(prompts, 16)
    assert e_on.metrics.drafted_tokens > 0, "spec never engaged"
    assert out_on == out_off
    # drained clean: no spec wait, no held pages, symmetric pending
    assert not e_on._spec_wait and not e_on._pending
    assert e_on.pool.pages_held == 0


@pytest.mark.parametrize("depth", [1, 2])
def test_spec_identity_sampled(depth):
    """Verification replays the sampled distribution too: spec-on output
    with temperature > 0 is identical to spec-off at both depths."""
    cfg = _cfg("full")
    rng = np.random.default_rng(14)
    prompts = [_rep_prompt(cfg.vocab_size),
               _prompts(rng, cfg.vocab_size, [8])[0]]
    # top_k=1 rides the sampled lowering (temp > 0 -> categorical) while
    # keeping the output cyclic enough for the bigram drafter to engage;
    # the second request samples freely and must match spec-off too
    samp = [SamplingParams(temperature=0.7, top_k=1, seed=42),
            SamplingParams(temperature=0.6, top_k=4, seed=42)]
    kw = dict(max_seq_len=48, max_new_tokens=14, spec_tokens=4)
    e_off = _engine(cfg, enable_spec=False, **kw)
    out_off = e_off.generate(prompts, 14, sampling=samp)
    e_on = _engine(cfg, depth=depth, params=e_off.params, enable_spec=True,
                   **kw)
    out_on = e_on.generate(prompts, 14, sampling=samp)
    assert e_on.metrics.drafted_tokens > 0
    assert out_on == out_off


def test_spec_acceptance_happens():
    """Greedy decode of a tiny model falls into an argmax cycle; once the
    generated history repeats, drafts are the model's own continuation
    and must be accepted (accept_rate > 0), shrinking decode dispatches
    without changing a single token."""
    cfg = _cfg("full")
    prompts = [_rep_prompt(cfg.vocab_size, n=8)]
    kw = dict(max_seq_len=64, max_new_tokens=32, spec_tokens=8)
    e_off = _engine(cfg, enable_spec=False, **kw)
    out_off = e_off.generate(prompts, 32)
    e_on = _engine(cfg, params=e_off.params, enable_spec=True, **kw)
    out_on = e_on.generate(prompts, 32)
    assert out_on == out_off
    m = e_on.metrics
    assert m.drafted_tokens > 0 and m.accepted_tokens > 0
    s = m.summary()
    assert 0.0 < s["accept_rate"] <= 1.0
    assert s["accepted_tokens"] == m.accepted_tokens


def test_spec_traced_phases_and_metrics(tmp_path):
    """Traced spec run: step.draft and verify.dispatch appear, the section
    spans still tile the step (coverage >= 0.95), and the spec counters
    flow through summary()."""
    from repro.obs import phase_coverage
    cfg = _cfg("full")
    eng = _engine(cfg, depth=2, max_seq_len=64, max_new_tokens=24,
                  spec_tokens=8, trace=True)
    eng.generate([_rep_prompt(cfg.vocab_size, n=8)], 24)
    tr = eng.tracer
    assert tr.open_spans() == []
    assert phase_coverage(tr) >= 0.95
    names = {e[1] for e in tr.events}
    assert {"step.draft", "verify.dispatch"} <= names
    s = eng.metrics.summary()
    assert s["drafted_tokens"] > 0
    assert s["verify_dispatch_s"] > 0 and s["draft_time_s"] > 0
