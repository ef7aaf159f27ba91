"""repro.api — the user-transparent Session facade.

Identity guarantees (the facade adds *zero* numerics of its own):
  * ``Session.train`` is loss-identical to driving ``TransparentTrainer``
    directly, on one device and under a 2x2 mesh;
  * ``Session.serve`` / ``Session.generate`` are token-identical to the raw
    ``ServingEngine``, on one device and under a 2x2 mesh;
plus capability dispatch: families that don't serve fail in one line at
load time (``require=``) or on first use, never mid-run.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.configs import MeshConfig, ServeConfig, get_config
from repro.configs.base import OptimizerConfig, RunConfig, ShapeConfig
from repro.core.transparent import TransparentTrainer
from repro.data.pipeline import make_input_pipeline
from repro.data.readers import synthetic_tokens
from repro.models import common, registry
from repro.serving import ServingEngine

ARCH = "stablelm-1.6b"
SERVE_ARCH = "qwen2.5-14b"


def _prompts(seed, vocab, lengths):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, vocab, (l,))) for l in lengths]


# ---------------------------------------------------------------------------
# load / parse_mesh / capabilities
# ---------------------------------------------------------------------------

def test_load_unknown_arch():
    with pytest.raises(KeyError, match="unknown arch"):
        api.load("no-such-arch")


def test_load_applies_model_overrides():
    base = api.load(ARCH, smoke=True)
    s = api.load(ARCH, smoke=True, num_layers=1)
    assert s.model.num_layers == 1 != base.model.num_layers
    assert s.model.name == base.model.name


def test_parse_mesh_forms():
    assert api.parse_mesh(None) is None
    assert api.parse_mesh("") is None
    m = api.parse_mesh("2x2")
    assert m.shape == (2, 2) and m.axis_names == ("data", "model")
    # pure-DP shorthand normalizes to a size-1 model axis: the sharding
    # rules always name "model", so a bare ("data",) mesh cannot run
    assert api.parse_mesh("4").shape == (4, 1)
    assert api.parse_mesh("4").axis_names == ("data", "model")
    assert api.parse_mesh("2x2x2").axis_names == ("pod", "data", "model")
    assert api.parse_mesh((2, 1)).shape == (2, 1)
    assert api.parse_mesh(m) is m
    with pytest.raises(ValueError, match="mesh"):
        api.parse_mesh("2xbanana")
    with pytest.raises(ValueError, match="mesh"):
        api.parse_mesh("2x2x2x2")


def test_pure_dp_mesh_shorthand_runs():
    """Regression: mesh='4' used to build a ('data',)-only mesh that the
    'model'-naming sharding rules rejected at first train/serve."""
    s = api.load(ARCH, smoke=True, mesh="4", num_layers=1)
    assert s.train(steps=1, seq_len=8, global_batch=8).step == 1
    assert len(s.generate([1, 2, 3], max_new=2)) == 2


def test_capabilities_by_family():
    assert api.load(SERVE_ARCH, smoke=True).capabilities() >= \
        {"train", "serve", "paged_serve"}
    # recurrent: serves, but O(1) state has nothing to page
    caps = api.load("rwkv6-1.6b", smoke=True).capabilities()
    assert "serve" in caps and "paged_serve" not in caps
    # encdec/vlm frontends need per-request modality inputs
    assert "serve" not in api.load("whisper-tiny", smoke=True).capabilities()


def test_capability_error_at_load_and_use():
    with pytest.raises(api.CapabilityError, match="doesn't serve"):
        api.load("whisper-tiny", smoke=True, require=("serve",))
    s = api.load("whisper-tiny", smoke=True)        # loading is fine
    with pytest.raises(api.CapabilityError, match="doesn't serve"):
        s.generate([1, 2, 3])
    with pytest.raises(api.CapabilityError, match="doesn't serve"):
        s.serve([[1, 2, 3]])
    # the engine itself dispatches on the declared capability set too
    with pytest.raises(ValueError, match="no serving"):
        ServingEngine(s.model, ServeConfig(max_batch=1, max_seq_len=16))


# ---------------------------------------------------------------------------
# train: loss-identical to the direct TransparentTrainer path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", [None, "2x2"])
def test_session_train_matches_transparent_trainer(mesh):
    steps, seq_len, batch = 3, 16, 8
    cfg = get_config(ARCH, smoke=True)
    ds = synthetic_tokens(cfg.vocab_size, seq_len, num_samples=batch * 8)

    session = api.load(ARCH, smoke=True, mesh=mesh)
    res = session.train(steps=steps, data=ds, seq_len=seq_len,
                        global_batch=batch)
    assert len(res.losses) == steps and res.step == steps

    # hand-wired reference: same bundle, same configs, same data pipeline
    bundle = registry.build(cfg)
    mesh_cfg = api.parse_mesh(mesh) or MeshConfig(
        shape=(1, 1), axis_names=("data", "model"))
    run = RunConfig(model=cfg,
                    shape=ShapeConfig("ref", "train", seq_len, batch),
                    mesh=mesh_cfg,
                    optimizer=OptimizerConfig(name="adam", lr=1e-3))
    trainer = TransparentTrainer.from_bundle(run, bundle)
    state = trainer.init(0)
    it, pf = make_input_pipeline(ds, batch, trainer.mesh, trainer.dp_axes)
    ref = []
    for _, b in zip(range(steps), it):
        state, m = trainer.step(state, b)
        ref.append(float(m["loss"]))
    pf.close()
    assert res.losses == ref                        # identical, not close


def test_session_train_continues_and_reports():
    session = api.load(ARCH, smoke=True)
    r1 = session.train(steps=2, seq_len=16, global_batch=8)
    r2 = session.train(steps=2, seq_len=16, global_batch=8)
    assert (r1.step, r2.step) == (2, 4)             # same state continues
    assert r2.metrics["step"] == 4
    assert "p50_s" in r1.straggler
    # continuation consumes the *next* batches, never a replay:
    # train(2) + train(2) is step-for-step identical to train(4)
    ref = api.load(ARCH, smoke=True).train(steps=4, seq_len=16,
                                           global_batch=8)
    assert r1.losses + r2.losses == ref.losses


# ---------------------------------------------------------------------------
# serve / generate: token-identical to the raw engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh", [None, "2x2"])
def test_session_serve_matches_raw_engine(mesh):
    scfg = ServeConfig(max_batch=2, max_seq_len=24, max_new_tokens=4,
                       decode_steps=2, page_size=8)
    session = api.load(SERVE_ARCH, smoke=True, mesh=mesh)
    prompts = _prompts(3, session.model.vocab_size, [5, 9, 7])
    out_api = session.serve(prompts, max_new=4, serve_cfg=scfg)

    raw = ServingEngine(get_config(SERVE_ARCH, smoke=True), scfg,
                        params=session.params,
                        mesh_cfg=api.parse_mesh(mesh))
    assert out_api == raw.generate(prompts, 4)


def test_meshed_session_params_are_born_sharded():
    """Under a mesh, ``Session.params`` comes out of init in the mesh
    layout: every leaf carries its ``logical_to_mesh`` sharding over all 4
    devices.  A whole copy on one device, sharded afterwards, does not fit
    beside its shards at published widths."""
    session = api.load(SERVE_ARCH, smoke=True, mesh="2x2")
    want = common.logical_to_mesh(
        session.bundle.specs, session.mesh,
        common.rules_for(session.mesh_cfg, session.model))
    split = 0
    for leaf, sh in zip(jax.tree.leaves(session.params),
                        jax.tree.leaves(want), strict=True):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        assert len(leaf.sharding.device_set) == 4
        split += leaf.addressable_shards[0].data.size < leaf.size
    assert split > 0                # the model axis really splits weights


def test_session_generate_single_prompt_and_batch():
    session = api.load(SERVE_ARCH, smoke=True)
    prompts = _prompts(5, session.model.vocab_size, [6, 8])
    single = session.generate(prompts[0], max_new=3)
    assert isinstance(single[0], int) and len(single) == 3
    batch = session.generate(prompts, max_new=3)
    assert batch[0] == single                       # same engine, same greedy
    # generate goes through the same engine as serve
    assert session.serve(prompts, max_new=3) == batch


def test_session_serve_engine_reuse_and_metrics():
    session = api.load(SERVE_ARCH, smoke=True)
    prompts = _prompts(7, session.model.vocab_size, [4, 6])
    session.serve(prompts, max_new=3)
    eng = session.engine
    assert eng is not None and eng.metrics.summary()["completed"] == 2
    session.serve(prompts, max_new=3)
    assert session.engine is eng                    # same knobs -> same engine
    assert session.engine.metrics.summary()["completed"] == 4
    # varying prompt lengths bucket into the same auto-sized engine
    session.serve(_prompts(8, session.model.vocab_size, [5, 9]), max_new=3)
    assert session.engine is eng


def test_session_serve_cfg_with_overrides_applied():
    from repro.configs import ServeConfig
    session = api.load(SERVE_ARCH, smoke=True)
    prompts = _prompts(9, session.model.vocab_size, [4])
    cfg = ServeConfig(max_batch=1, max_seq_len=16, max_new_tokens=4,
                      page_size=8)
    session.serve(prompts, max_new=2, serve_cfg=cfg, kv_layout="slotted")
    assert session.engine.cfg.kv_layout == "slotted"   # override not dropped
    assert session.engine.cfg.max_seq_len == 16        # base cfg kept


def test_trained_params_flow_into_serving():
    session = api.load(ARCH, smoke=True)
    before = session.generate([1, 2, 3, 4], max_new=3)
    session.train(steps=5, seq_len=16, global_batch=8)
    after = session.generate([1, 2, 3, 4], max_new=3)
    # engines are rebuilt on the trained params (greedy argmax may or may
    # not move for so few steps; the engine cache must have been dropped)
    raw = ServingEngine(session.model,
                        session.engine.cfg, params=session.params)
    assert after == raw.generate([[1, 2, 3, 4]], 3)[0]
    assert len(before) == len(after) == 3
