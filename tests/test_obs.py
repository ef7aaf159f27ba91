"""Engine tracing + per-phase attribution (repro.obs).

Layers of guarantees:
  * tracer mechanics — deterministic timelines under an injectable clock,
    span open/close balance (including stale re-opens and mid-flight
    close_all), bounded ring buffer, engine-track-only phase accounting;
  * exporters — Chrome trace-event schema validity (every event carries
    ph/ts/pid/tid; one track per request; metadata names), phase snapshot
    / coverage math;
  * disabled path — NULL_TRACER is a strict no-op (shared span singleton,
    no events, zero phase time) and an untraced engine records nothing,
    creating no profiler annotation, while no profiler session runs;
  * end-to-end — a traced engine run keeps every lifecycle span balanced
    through preemption and chunked prefill, its section spans cover
    >= 95% of the engine-loop wall, the emitted trace loads as JSON, and
    nothing is fenced;
  * profiler sessions — an untraced engine's spans land on the profile as
    ``serve.*`` annotations, a constant offset from the process-wide
    record ``profiled()``, which holds the counters the benchmark reads;
    the per-request queue / prefill split is exact on a fake clock; the
    model's layer kinds are named scopes in the lowered program;
  * the training loop — ``Session.train``'s ``train.*`` spans balance and
    its counters count under a profiler session, nothing is recorded
    without one, and the injected all-reduce and the init broadcast are
    named scopes of the lowered programs.
"""
import json
import re

import jax
import numpy as np
import pytest

from repro.configs import ServeConfig, get_config
from repro.models import registry
from repro.obs import (ENGINE_TRACK, NULL_TRACER, NullTracer, Tracer,
                       chrome_trace, phase_coverage, phase_snapshot,
                       profiled, request_track, write_chrome_trace)
from repro.serving import ServingEngine, ServingMetrics


class FakeClock:
    """Deterministic monotone clock: every read advances by ``tick``."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def _prompts(rng, vocab, lengths):
    return [list(rng.integers(0, vocab, (l,))) for l in lengths]


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------

def test_tracer_deterministic_timeline():
    clk = FakeClock()
    tr = Tracer(clock=clk)            # reset() reads the clock once (t0=1)
    with tr.span("step"):             # enter t=2, exit t=3
        pass
    assert tr.phase_seconds == {"step": 1.0}
    assert tr.phase_counts == {"step": 1}
    ph, name, track, ts, dur, args = tr.events[-1]
    assert (ph, name, track, ts, dur, args) == \
        ("X", "step", ENGINE_TRACK, 2.0, 1.0, None)


def test_tracer_nested_spans_accumulate_independently():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("step"):                       # t=2 .. t=5
        with tr.span("decode.dispatch"):          # t=3 .. t=4
            pass
    assert tr.phase_seconds["step"] == 3.0
    assert tr.phase_seconds["decode.dispatch"] == 1.0
    # inner span closes first: events land in completion order
    assert [e[1] for e in tr.events] == ["decode.dispatch", "step"]


def test_tracer_request_track_spans_do_not_count_as_phases():
    tr = Tracer(clock=FakeClock())
    with tr.span("decode", track=request_track(7)):
        pass
    assert tr.phase_seconds == {}               # engine track only
    assert tr.events[-1][2] == "req7"


def test_tracer_begin_end_balance():
    tr = Tracer(clock=FakeClock())
    rt = request_track(0)
    tr.begin("queued", track=rt)
    assert tr.open_spans() == [(rt, "queued")]
    assert tr.end("queued", track=rt) is True
    assert tr.open_spans() == []
    # closing a never-opened span is a silent no-op (preemption paths
    # close "whichever of prefill/decode is open" unconditionally)
    assert tr.end("decode", track=rt) is False
    assert all(e[1] != "decode" for e in tr.events)


def test_tracer_reopen_closes_stale_span():
    tr = Tracer(clock=FakeClock())
    tr.begin("prefill", track="req1")
    tr.begin("prefill", track="req1")           # stale: auto-closed
    spans = [e for e in tr.events if e[0] == "X"]
    assert len(spans) == 1 and spans[0][5]["reopened"] is True
    assert tr.open_spans() == [("req1", "prefill")]
    tr.close_all(drained=True)
    assert tr.open_spans() == []


def test_tracer_end_merges_args():
    tr = Tracer(clock=FakeClock())
    tr.begin("decode", track="req2", slot=3)
    tr.end("decode", track="req2", tokens=8)
    assert tr.events[-1][5] == {"slot": 3, "tokens": 8}


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(10):
        tr.instant("ev", i=i)
    assert len(tr.events) == 4 and tr.dropped == 6
    assert [e[5]["i"] for e in tr.events] == [6, 7, 8, 9]   # oldest dropped


def test_tracer_reset_keeps_clock_and_meta():
    clk = FakeClock()
    tr = Tracer(clock=clk, meta={"model": "m"})
    tr.instant("x")
    tr.begin("queued", track="req0")
    tr.reset()
    assert not tr.events and tr.open_spans() == [] and tr.dropped == 0
    assert tr.meta == {"model": "m"} and tr.now() > 0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def _fake_traced_run():
    """A hand-driven timeline exercising every event kind."""
    tr = Tracer(clock=FakeClock(), meta={"model": "fake"})
    with tr.span("step"):
        with tr.span("admit"):
            tr.instant("pool.page_alloc", page=1, slot=0)
        with tr.span("decode.dispatch"):
            pass
        tr.counter("queue_depth", 3)
    tr.begin("decode", track=request_track(0))
    return tr


def test_chrome_trace_schema():
    tr = _fake_traced_run()
    doc = chrome_trace(tr)
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    for e in evs:
        assert {"ph", "ts", "pid", "tid"} <= set(e), e
    # instant events are scoped; counters carry their value
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["page"] == 1
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["args"]["value"] == 3.0
    # the still-open request span exports as unfinished, not a dangling B
    open_ev = next(e for e in evs if e.get("args", {}).get("unfinished"))
    assert open_ev["ph"] == "X" and open_ev["name"] == "decode"
    assert not any(e["ph"] == "B" for e in evs)
    # engine track is tid 0; the request track got its own tid + name
    names = {e["args"]["name"]: e["tid"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[ENGINE_TRACK] == 0 and "req0" in names
    assert doc["otherData"]["model"] == "fake"
    json.dumps(doc)                               # serializable end to end


def test_write_chrome_trace_roundtrip(tmp_path):
    tr = _fake_traced_run()
    tr.close_all()       # an open span's export reads the (advancing) clock
    p = write_chrome_trace(tr, str(tmp_path / "t.json"))
    loaded = json.loads((tmp_path / "t.json").read_text())
    assert p.endswith("t.json")
    assert loaded == json.loads(json.dumps(chrome_trace(tr)))


def test_phase_snapshot_and_coverage_math():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("step"):                        # 11 ticks total
        with tr.span("step.plan"):               # pipeline section: 3 ticks
            with tr.span("plan"):                # leaf inside a section
                pass
        with tr.span("step.submit"):             # section: 3 ticks
            with tr.span("decode.dispatch"):     # leaf: 1 tick
                pass
        with tr.span("step.retire"):             # section: 1 tick
            pass
    snap = phase_snapshot(tr)
    assert snap["step_time_s"] == 11.0
    assert snap["plan_time_s"] == 1.0
    assert snap["decode_dispatch_s"] == 1.0
    assert snap["prefill_dispatch_s"] == 0.0
    assert snap["other_time_s"] == 9.0           # step - leaves
    assert snap["host_overhead_frac"] == pytest.approx(9.0 / 11.0)
    # coverage counts the pipeline sections (3 + 3 + 1 = 7) over step
    assert phase_coverage(tr) == pytest.approx(7.0 / 11.0)
    assert phase_coverage(Tracer(clock=FakeClock())) == 1.0   # nothing traced


# ---------------------------------------------------------------------------
# Disabled path (NULL_TRACER)
# ---------------------------------------------------------------------------

def test_null_tracer_is_strict_noop():
    n = NULL_TRACER
    assert isinstance(n, NullTracer) and n.enabled is False
    # one shared context-manager singleton: no per-call allocation
    assert n.span("a") is n.span("b", track="req1", x=1)
    with n.span("step"):
        pass
    n.begin("queued", track="req0")
    assert n.end("queued", track="req0") is False
    n.instant("pool.cow", src=1, dst=2)
    n.counter("queue_depth", 5)
    assert n.events == () and n.phase_seconds == {} and n.open_spans() == []
    assert n.close_all() == 0 and n.now() == 0.0
    # exporters accept it without branches
    assert phase_snapshot(n) == {"step_time_s": 0.0, "plan_time_s": 0.0,
                                 "draft_time_s": 0.0,
                                 "prefill_dispatch_s": 0.0,
                                 "decode_dispatch_s": 0.0,
                                 "verify_dispatch_s": 0.0,
                                 "retire_wait_s": 0.0,
                                 "other_time_s": 0.0,
                                 "host_overhead_frac": 0.0}
    assert phase_coverage(n) == 1.0


def test_metrics_summary_stable_schema_untraced():
    """Rates and phase times report honest zeros untraced; int counters
    stay ints; a rejected-everything run divides nothing by zero."""
    m = ServingMetrics(clock=FakeClock())      # tracer=None -> NULL path
    m.record_reject()
    s = m.summary()
    assert s["rejected"] == 1 and s["elapsed_s"] == 0.0
    assert s["tokens_per_sec"] == 0.0
    assert s["step_time_s"] == 0.0 and s["other_time_s"] == 0.0
    assert isinstance(s["completed"], int)
    assert isinstance(s["decode_tokens"], int)


# ---------------------------------------------------------------------------
# End-to-end: traced engine runs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("qwen2.5-14b", smoke=True)
    bundle = registry.build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return cfg, params


def test_engine_untraced_records_nothing(dense_setup):
    cfg, params = dense_setup
    scfg = ServeConfig(max_batch=2, max_seq_len=32, max_new_tokens=4,
                       decode_steps=2, page_size=8)
    eng = ServingEngine(cfg, scfg, params=params)
    assert eng.tracer is NULL_TRACER
    rng = np.random.default_rng(0)
    eng.generate(_prompts(rng, cfg.vocab_size, [5, 9]), 4)
    assert eng.tracer.events == ()
    assert eng.save_trace("/nonexistent/never-written.json") is None
    s = eng.metrics.summary()
    assert s["step_time_s"] == 0.0 and s["retire_wait_s"] == 0.0


def test_engine_traced_spans_balance_and_cover(dense_setup, tmp_path):
    """Chunked prefill + prefix sharing + mid-prefill completion, traced:
    lifecycle spans all close, sections cover >= 95% of the step wall,
    and the trace exports schema-valid."""
    cfg, params = dense_setup
    scfg = ServeConfig(max_batch=2, max_seq_len=64, max_new_tokens=4,
                       decode_steps=2, page_size=8,
                       prefill_chunk_tokens=8, enable_prefix_cache=True,
                       trace=True)
    eng = ServingEngine(cfg, scfg, params=params)
    assert eng.tracer.enabled and eng.paged
    rng = np.random.default_rng(3)
    shared = list(rng.integers(0, cfg.vocab_size, (16,)))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, (t,)))
               for t in (9, 5, 13)] + [[7] * 3]
    outs = eng.generate(prompts, 4)
    assert all(len(o) == 4 for o in outs)
    tr = eng.tracer
    assert tr.open_spans() == []                 # drained -> balanced
    assert phase_coverage(tr) >= 0.95
    s = eng.metrics.summary()
    assert s["step_time_s"] > 0
    assert s["step_time_s"] == pytest.approx(
        s["plan_time_s"] + s["draft_time_s"] + s["prefill_dispatch_s"]
        + s["decode_dispatch_s"] + s["verify_dispatch_s"]
        + s["retire_wait_s"] + s["other_time_s"])
    # every decode-loop token is attributed; first tokens come from prefill
    assert s["decode_tokens"] == s["tokens_out"] - s["completed"]
    assert s["decode_dispatch_s"] > 0 and s["prefill_dispatch_s"] > 0
    assert s["retire_wait_s"] > 0
    names = {e[1] for e in tr.events}
    assert {"step", "step.plan", "step.submit", "step.retire", "admit",
            "prefill", "decode.dispatch", "plan", "prefill.dispatch",
            "retire.wait", "request.admit", "request.first_token",
            "prefill.chunk", "queued", "decode", "request.complete",
            "pool.page_alloc", "pool.prefix_hit"} <= names
    # one lifecycle track per request, all schema-valid
    doc = json.loads(write_chrome_trace(tr, str(tmp_path / "e.json"))
                     and (tmp_path / "e.json").read_text())
    evs = doc["traceEvents"]
    assert all({"ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    tracks = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {request_track(r) for r in range(len(prompts))} <= tracks


def test_engine_traced_preemption_keeps_spans_balanced(dense_setup):
    """Page pressure forces eviction mid-run (including mid-prefill): the
    victim's open span closes (preempted=True), it re-queues, and the
    drained engine ends with zero open spans and identical tokens."""
    cfg, params = dense_setup
    base = ServeConfig(max_batch=2, max_seq_len=32, max_new_tokens=12,
                       decode_steps=2, kv_layout="paged", page_size=4,
                       num_pages=12)             # worst case would need 17
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, cfg.vocab_size, [14, 15])
    traced = ServingEngine(cfg, base.replace(trace=True), params=params)
    outs = traced.generate(prompts, 12)
    assert traced.metrics.preemptions >= 1
    tr = traced.tracer
    assert tr.open_spans() == []
    names = {e[1] for e in tr.events}
    assert "request.preempt" in names and "queue.push_front" in names
    preempted = [e for e in tr.events
                 if e[0] == "X" and e[5] and e[5].get("preempted")]
    assert preempted, "no span recorded the preemption"
    # tracing must not perturb scheduling decisions or tokens
    assert outs == ServingEngine(cfg, base, params=params).generate(
        prompts, 12)
    json.dumps(chrome_trace(tr))


def test_serve_config_trace_knobs_validate():
    ServeConfig(trace=True, trace_capacity=1024).validate()
    with pytest.raises(ValueError):
        ServeConfig(trace_capacity=0).validate()
    with pytest.raises(ValueError):
        ServeConfig(trace="yes").validate()


def test_session_trace_passthrough(tmp_path):
    """The Session surface: serve(..., trace=True) keys a traced engine,
    session.save_trace writes the Perfetto JSON."""
    from repro.api import load
    sess = load("qwen2.5-14b", smoke=True, require=("serve",))
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, sess.model.vocab_size, [6, 10])
    sess.serve(prompts, max_new=3)
    assert sess.tracer is NULL_TRACER and sess.save_trace("x") is None
    sess.serve(prompts, max_new=3, trace=True)
    assert sess.tracer.enabled
    p = sess.save_trace(str(tmp_path / "s.json"))
    doc = json.loads((tmp_path / "s.json").read_text())
    assert p and doc["traceEvents"]


# ---------------------------------------------------------------------------
# No fence, and the profiler-gated path
# ---------------------------------------------------------------------------

def test_profiler_gate_is_tracemes_own_check():
    """The gate is the profiler's own TraceMe.is_enabled, imported from
    jax._src.lib._profiler in one place; off outside a session."""
    from jax._src.lib import _profiler

    from repro.obs import trace as obs_trace
    assert obs_trace.profiling is _profiler.TraceMe.is_enabled
    assert obs_trace.profiling() is False


def test_traced_engine_never_blocks_until_ready(dense_setup, monkeypatch):
    """A traced serve dispatches exactly like an untraced one: it never
    calls jax.block_until_ready (the sync is retire's np.asarray)."""
    cfg, params = dense_setup
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    scfg = ServeConfig(max_batch=2, max_seq_len=64, max_new_tokens=6,
                       decode_steps=2, page_size=8, prefill_chunk_tokens=8,
                       trace=True)
    eng = ServingEngine(cfg, scfg, params=params)
    rng = np.random.default_rng(2)
    outs = eng.generate(_prompts(rng, cfg.vocab_size, [5, 20, 9]), 6)
    assert calls == []
    assert eng.tracer.phase_counts["retire.wait"] > 0
    assert outs == ServingEngine(cfg, scfg.replace(trace=False),
                                 params=params).generate(
        _prompts(np.random.default_rng(2), cfg.vocab_size, [5, 20, 9]), 6)


def test_untraced_unprofiled_engine_creates_no_annotation(dense_setup,
                                                          monkeypatch):
    """trace=False and no profiler session: nothing recorded anywhere,
    and no TraceAnnotation is ever created."""
    from repro.obs import trace as obs_trace
    made = []

    class Counting(obs_trace.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    profiled().clear()
    cfg, params = dense_setup
    eng = ServingEngine(cfg, ServeConfig(max_batch=2, max_seq_len=32,
                                         max_new_tokens=4, decode_steps=2,
                                         page_size=8), params=params)
    eng.generate(_prompts(np.random.default_rng(0), cfg.vocab_size, [5, 9]),
                 4)
    assert made == []
    assert len(profiled().events) == 0 and eng.tracer.events == ()


@pytest.fixture(scope="module")
def profiled_serve(dense_setup, tmp_path_factory):
    """One untraced serve under a CPU profiler session: the record it
    left in profiled(), and the serve.* host events of the .xplane.pb."""
    from jax.profiler import ProfileData
    cfg, params = dense_setup
    scfg = ServeConfig(max_batch=2, max_seq_len=64, max_new_tokens=6,
                       decode_steps=2, page_size=8)
    eng = ServingEngine(cfg, scfg, params=params)
    rng = np.random.default_rng(4)
    # compile outside the session; fresh prompts of the same lengths then
    # run the same programs with no prefix-cache hits
    eng.generate(_prompts(rng, cfg.vocab_size, [5, 12, 9]), 6)
    log_dir = tmp_path_factory.mktemp("prof")
    profiled().clear()
    jax.profiler.start_trace(str(log_dir))
    try:
        eng.generate(_prompts(rng, cfg.vocab_size, [5, 12, 9]), 6)
    finally:
        jax.profiler.stop_trace()
    events = list(profiled().events)
    profiled().clear()
    pd = ProfileData.from_file(str(next(log_dir.rglob("*.xplane.pb"))))
    host = [(e.name, e.start_ns, e.duration_ns) for plane in pd.planes
            if plane.name.startswith("/host:CPU") for line in plane.lines
            for e in line.events if e.name.startswith("serve.")]
    return events, host


def test_profiled_record_holds_engine_spans_and_counters(profiled_serve):
    events, _ = profiled_serve
    names = {(e[0], e[1]) for e in events}
    assert {("X", "step"), ("X", "step.submit"), ("X", "retire.wait"),
            ("i", "request.admit"),
            ("i", "request.first_token")} <= names
    admits = [e[5] for e in events if e[1] == "request.admit"]
    firsts = [e[5] for e in events if e[1] == "request.first_token"]
    assert len(admits) == len(firsts) == 3
    assert all(a["queue_s"] >= 0 for a in admits)
    assert all(f["prefill_s"] > 0 for f in firsts)
    subs = [e[5] for e in events if e[1] == "step.submit"]
    assert sum(a["prefill_tokens"] for a in subs) == 5 + 12 + 9
    assert any(a["decode_rows"] > 0 for a in subs)
    # spans carry perf_counter_ns stamps and ns durations
    steps = [e for e in events if e[1] == "step"]
    assert all(isinstance(e[3], int) and e[4] > 0 for e in steps)


def test_profile_spans_share_the_records_clock(profiled_serve):
    """Each serve.step event of the profile's host plane starts a constant
    offset from its record's perf_counter_ns start, within 50 us."""
    events, host = profiled_serve
    rec = sorted(e[3] for e in events if e[0] == "X" and e[1] == "step")
    prof = sorted(s for n, s, _ in host if n == "serve.step")
    assert len(prof) == len(rec) > 3
    offsets = [p - r for p, r in zip(prof, rec)]
    assert max(offsets) - min(offsets) < 50_000
    assert {"serve.step.plan", "serve.step.submit", "serve.retire.wait",
            "serve.decode.dispatch"} <= {n for n, _, _ in host}


def test_queue_and_prefill_split_ttft_exactly():
    """On a fake clock queue_s + prefill_s is submit-to-first-token
    exactly, and a preempted request's next admission waits from its
    requeue, not from its submit."""
    clk = FakeClock()
    m = ServingMetrics(clock=clk)
    m.record_submit(0)                          # t=1
    clk.t = 4.0
    assert m.record_admit(0) == 4.0             # t=5
    clk.t = 9.0
    assert m.record_first_token(0) == 5.0       # t=10
    assert m.ttft == [9.0] == [4.0 + 5.0]
    clk.t = 19.0
    m.record_preemption(0)                      # requeued at t=20
    clk.t = 26.0
    assert m.record_admit(0) == 7.0             # t=27


def test_engine_admit_and_first_token_events_on_a_fake_clock(dense_setup):
    """The engine's request.admit / request.first_token events carry the
    split: per request, queue_s + prefill_s is its TTFT exactly; a
    preempted request's second admission waits from its requeue."""
    cfg, params = dense_setup
    base = ServeConfig(max_batch=2, max_seq_len=32, max_new_tokens=12,
                       decode_steps=2, kv_layout="paged", page_size=4,
                       num_pages=12, trace=True)
    eng = ServingEngine(cfg, base, params=params, clock=FakeClock())
    eng.generate(_prompts(np.random.default_rng(11), cfg.vocab_size,
                          [14, 15]), 12)
    assert eng.metrics.preemptions >= 1
    evs = eng.tracer.events
    admits = [e for e in evs if e[1] == "request.admit"]
    firsts = {e[5]["rid"]: e[5]["prefill_s"] for e in evs
              if e[1] == "request.first_token"}
    first_admit = {}
    for e in admits:
        first_admit.setdefault(e[5]["rid"], e[5]["queue_s"])
    split = sorted(first_admit[r] + firsts[r] for r in firsts)
    assert split == sorted(eng.metrics.ttft)
    # the victim's re-admission: its wait runs from the preemption
    preempt = next(e for e in evs if e[1] == "request.preempt")
    rid = preempt[5]["rid"]
    again = [e for e in admits if e[5]["rid"] == rid][1]
    requeued_at = again[3] - again[5]["queue_s"]
    assert abs(requeued_at - preempt[3]) <= 3  # a few clock reads apart
    assert again[5]["queue_s"] < again[3] - admits[0][3]


def test_lowered_decode_scan_names_its_layer_kinds(dense_setup):
    """The shared block and the sampler run under named scopes, so the
    device trace's op metadata says which layer kind each fusion is."""
    cfg, params = dense_setup
    from repro.serving.sampling import GREEDY, pack_params
    eng = ServingEngine(cfg, ServeConfig(max_batch=2, max_seq_len=32,
                                         page_size=8), params=params)
    packed = eng.pool.decode_operands({0: 4}, mask_slots=(1,))
    samp = jax.numpy.asarray(np.stack([pack_params(GREEDY)] * 2))
    hlo = eng._decode_sampled.lower(
        eng.params, eng._last_toks_dev, eng.pool.pages, packed,
        samp).compile().as_text()
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("attention", "mlp", "norm", "lm_head", "sampler"):
        assert f"/{scope}/" in op_names, scope


# ---------------------------------------------------------------------------
# The training loop's spans and counters
# ---------------------------------------------------------------------------

TRAIN_KW = dict(seq_len=16, global_batch=4)


@pytest.fixture(scope="module")
def profiled_train(tmp_path_factory):
    """Three ``Session.train`` steps under a CPU profiler session (compiled
    before it): the record they left in profiled(), and the train.* host
    events of the .xplane.pb."""
    from jax.profiler import ProfileData

    from repro import api
    session = api.load("stablelm-1.6b", smoke=True)
    session.train(steps=1, **TRAIN_KW)
    log_dir = tmp_path_factory.mktemp("prof_train")
    profiled().clear()
    jax.profiler.start_trace(str(log_dir))
    try:
        session.train(steps=3, **TRAIN_KW)
    finally:
        jax.profiler.stop_trace()
    events = list(profiled().events)
    profiled().clear()
    pd = ProfileData.from_file(str(next(log_dir.rglob("*.xplane.pb"))))
    host = [e.name for plane in pd.planes
            if plane.name.startswith("/host:CPU") for line in plane.lines
            for e in line.events if e.name.startswith("train.")]
    return events, host


def test_train_spans_balance_and_counters_count(profiled_train):
    """Each step is a data fetch, an enqueue and a sync, in that order and
    apart; the counters give the state's step and the tokens it took."""
    from repro.obs import TRAIN_TRACK
    events, host = profiled_train
    spans = sorted((e for e in events if e[0] == "X"), key=lambda e: e[3])
    assert [e[1] for e in spans] == ["train.data", "train.step",
                                     "train.sync"] * 3
    assert all(e[2] == TRAIN_TRACK and e[4] > 0 for e in spans)
    assert all(a[3] + a[4] <= b[3] for a, b in zip(spans, spans[1:]))
    counters = {}
    for e in events:
        if e[0] == "C":
            counters.setdefault(e[1], []).append(e[4])
    assert counters == {"train.steps": [2.0, 3.0, 4.0],
                        "train.tokens": [2.0 * 64, 3.0 * 64, 4.0 * 64]}
    for name in ("train.data", "train.step", "train.sync"):
        assert host.count(name) == 3, (name, host)


def test_untraced_train_records_nothing(monkeypatch):
    """No profiler session: the training loop creates no annotation and
    records nothing."""
    from repro import api
    from repro.obs import trace as obs_trace
    made = []

    class Counting(obs_trace.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    profiled().clear()
    api.load("stablelm-1.6b", smoke=True).train(steps=2, **TRAIN_KW)
    assert made == [] and len(profiled().events) == 0


def _op_names(hlo, op):
    return [n for line in hlo.splitlines() if f" {op}(" in line
            or f" {op}-start(" in line
            for n in re.findall(r'op_name="([^"]*)"', line)]


def test_lowered_dp_step_names_its_allreduce():
    """The injected gradient reduction and the init broadcast run under
    named scopes, so the device trace's op metadata names them."""
    from jax.sharding import PartitionSpec as P

    from repro import api
    from repro.configs.base import ShapeConfig
    from repro.core import broadcast
    from repro.launch.mesh import auto_mesh
    session = api.load("stablelm-1.6b", smoke=True, mesh="4x1")
    session.train(steps=1, **TRAIN_KW)
    hlo = session.trainer.lower_step(session.bundle.train_input_specs(
        ShapeConfig("t", "train", 16, 4))).compile().as_text()
    assert any("/allreduce/" in n for n in _op_names(hlo, "all-reduce"))
    mesh = auto_mesh((4, 1), ("data", "model"))
    fn = jax.shard_map(lambda x: broadcast.broadcast_from_rank0(x, ("data",)),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False, axis_names={"data"})
    hlo = jax.jit(fn).lower(jax.numpy.ones(8)).compile().as_text()
    assert any("/broadcast/" in n for n in _op_names(hlo, "all-reduce"))
