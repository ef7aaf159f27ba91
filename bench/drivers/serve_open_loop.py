"""Open-loop serving: requests arrive on the mix's schedule whether or not
earlier ones are done, into the program's continuous-batching engine.

Set-up loads the model through ``repro.api``, makes the weights from the
seed (``bench/reference/dense.make_params``), builds the engine that
``Session.serve`` builds for the configuration's ``ServeConfig``, and warms
up the prefill buckets the mix's prompts fall in, the greedy and sampled
decode programs and the speculative verify at each batch size.  The generator then starts;
after the mix's ``warmup_s`` the window opens.  Each request is timed from
when it was due, not from when it was submitted.  After the window the
engine drains, its state is freed, and the plain reference reads the
greedy requests of a seeded sample (the longest among them).
"""
from __future__ import annotations

import functools
import gc
import math
import shutil
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

from bench import harness, traffic
from bench.reference import dense

#: seconds the engine may take after the window to finish what was due in it
DRAIN_LIMIT_S = 60.0
#: greedy requests the correctness check reads, besides the longest
CHECK_SAMPLE = 6


def percentile(xs, p):
    """Nearest-rank percentile (as ``repro.serving.metrics.percentile``)."""
    if not xs:
        return float("nan")
    s = sorted(xs)
    k = max(math.ceil(p / 100.0 * len(s)), 1) - 1
    return s[min(k, len(s) - 1)]


def build(cfg: dict, seed: int):
    """(session, engine): the configuration's model with the benchmark's
    weights behind the engine ``Session.serve`` would build."""
    from repro import api
    from repro.configs.base import ServeConfig
    from repro.serving import ServingEngine
    load = cfg["load"]
    session = api.load(load["arch"], smoke=load.get("smoke", False),
                       seed=seed, **load.get("overrides", {}))
    check_widths(cfg["model"], session.model)
    params = dense.make_params(cfg["model"], seed)
    engine = ServingEngine(session.model, ServeConfig(**cfg["serve"]),
                           params=params, seed=seed)
    return session, engine


def check_widths(m: dict, model) -> None:
    """The program runs the sizes the configuration file states."""
    k = dense.dims(m)
    got = {"D": model.d_model, "H": model.num_heads,
           "KV": model.num_kv_heads, "hd": model.resolved_head_dim,
           "F": model.d_ff, "V": model.vocab_size, "L": model.num_layers,
           "theta": float(model.rope_theta), "eps": float(model.norm_eps),
           "bias": bool(model.qkv_bias),
           "norm": "rms" if model.norm == "rmsnorm" else "layer"}
    bad = {x: (k[x], got[x]) for x in k if k[x] != got[x]}
    if bad:
        raise SystemExit(f"bench: the program's model differs from the "
                         f"configuration file (file, program): {bad}")


def warm_up(engine, mix: dict, vocab: int, seed: int) -> None:
    """Compile what the mix will run: a greedy and then a sampled prompt in
    each prefill bucket its lengths reach (so both the all-greedy and the
    sampling decode programs compile), and what speculation runs."""
    from repro.serving import SamplingParams
    rng = np.random.default_rng(seed ^ 0x5EED)
    samp = SamplingParams(**mix["sampling"], seed=1)
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    n = 16
    lengths = []
    while n // 2 < hi:
        lengths.append(min(max(n, lo), hi))
        n *= 2
    for sp in (None, samp):
        for length in sorted(set(lengths)):
            engine.submit(rng.integers(0, vocab, length).tolist(), 4,
                          sampling=sp)
        engine.run()
    warm_speculation(engine)
    engine.metrics.reset()


def warm_speculation(engine) -> None:
    """Compile what speculation runs, which the engine compiles only when
    drafts come, and random prompts draft rarely and late.  The verify
    program runs once for each power-of-two row count up to the slot
    count, on inert rows as the engine pads them (page table 0 routes
    writes to the trash page, n_valid 0 masks every position, drafts -1
    reject); then an idle slot's next token is set from a host integer,
    as accepting drafts does."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import PACKED_WIDTH
    if getattr(engine, "_verify", None) is None:
        return
    width = engine.cfg.spec_tokens + 1
    tw = engine.pool.table_width
    rows = 1
    while rows <= engine.cfg.max_batch:
        toks = np.zeros((rows, width), np.int32)
        packed = np.zeros((rows, tw + 2 + PACKED_WIDTH + width - 1), np.int32)
        packed[:, tw + 2 + PACKED_WIDTH:] = -1
        emit, _, engine.pool.pages = engine._verify(
            engine.params, jnp.asarray(toks), engine.pool.pages,
            jnp.asarray(packed))
        jax.block_until_ready(emit)
        rows *= 2
    engine._last_toks_dev = engine._set_tok(engine._last_toks_dev, 0, 0)


class Recorder:
    """Host times of every token the stream hands out."""

    def __init__(self):
        import jax
        self.times = defaultdict(list)        # rid -> [t, ...]
        self._ann = jax.profiler.TraceAnnotation

    def __call__(self, rid, token, done):
        with self._ann("bench.stream"):
            self.times[rid].append(time.perf_counter())


class Window:
    """One open-loop window: the requests, when each was due, submitted
    and answered, and the engine's counts over the traced part."""

    def __init__(self, reqs, seconds):
        self.reqs, self.seconds = reqs, seconds
        self.rec = Recorder()
        self.rid_of, self.submit_at, self.rejected = {}, {}, []
        self.counts = {}
        self.compiles = []
        self.t0 = self.t_open = self.t_close = 0.0
        self.due_in = []

    def due(self, i):
        return self.t0 + self.reqs[i].due_s


def _snapshot(engine):
    mt = engine.metrics
    return {"prefill_tokens": mt.prefill_tokens,
            "prefix_hit_tokens": mt.prefix_hit_tokens,
            "decode_tokens": mt.decode_tokens,
            "drafted_tokens": mt.drafted_tokens,
            "accepted_tokens": mt.accepted_tokens,
            "t": time.perf_counter()}


def open_loop(engine, mix: dict, reqs, seconds: float, trace_dir=None):
    """Submit ``reqs`` on their schedule into ``engine`` from now, through
    the mix's warm-up and then ``seconds`` of window; then let the engine
    drain what was due.  With ``trace_dir`` a profiler trace covers a few
    seconds inside the window."""
    import jax
    from repro.serving import SamplingParams
    sampling = {i: SamplingParams(**mix["sampling"], seed=r.sample_seed)
                for i, r in enumerate(reqs) if not r.greedy}
    w = Window(reqs, seconds)
    clock = harness.CompileClock()
    ann = jax.profiler.TraceAnnotation
    w.t0 = time.perf_counter()
    w.t_open = w.t0 + float(mix.get("warmup_s", 0.0))
    w.t_close = w.t_open + seconds
    trace_from = w.t_open + 0.3 * seconds
    trace_to = trace_from + min(float(mix.get("trace_s", 3.0)), 0.5 * seconds)
    tracing, compiles0, nxt = None, None, 0
    while True:
        now = time.perf_counter()
        if now >= w.t_close:
            break
        if compiles0 is None and now >= w.t_open:
            compiles0 = clock.events
        if trace_dir and tracing is None and not w.counts \
                and now >= trace_from:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            tracing = ann("bench.trace_window")
            tracing.__enter__()
            w.counts["before"] = _snapshot(engine)
        if tracing is not None and now >= trace_to:
            w.counts["after"] = _snapshot(engine)
            tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = None
        with ann("bench.submit"):
            nxt = submit_due(engine, w, sampling, nxt, now)
        if engine.busy:
            with ann("bench.engine_step"):
                engine.step(w.rec)
        else:
            wake = [w.t_close] + [t for t in (trace_from, trace_to)
                                  if trace_dir and t > now]
            if nxt < len(reqs):
                wake.append(w.due(nxt))
            with ann("bench.wait_arrival"):
                time.sleep(max(0.0, min(wake) - time.perf_counter()))
    if tracing is not None:
        w.counts["after"] = _snapshot(engine)
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    w.compiles = sorted(set(clock.traced[compiles0 or 0:]))
    # what fell due while the host was inside the last step goes in late,
    # not missing: its wait counts in its TTFT
    nxt = submit_due(engine, w, sampling, nxt, w.t_close)
    w.due_in = [i for i in range(nxt) if w.t_open <= w.due(i) < w.t_close]
    t_drain = time.perf_counter()
    while engine.busy and time.perf_counter() - t_drain < DRAIN_LIMIT_S:
        engine.step(w.rec)
    return w


def submit_due(engine, w: Window, sampling: dict, nxt: int,
               until: float) -> int:
    """Submit the requests from ``nxt`` on that fell due before ``until``;
    returns the index of the next one."""
    while nxt < len(w.reqs) and w.due(nxt) < until:
        r = w.reqs[nxt]
        rid = engine.submit(r.prompt, r.max_new, sampling=sampling.get(nxt))
        w.submit_at[nxt] = time.perf_counter()
        if rid is None:
            w.rejected.append(nxt)
        else:
            w.rid_of[nxt] = rid
        nxt += 1
    return nxt


def summarize(w: Window, engine) -> dict:
    """The end-to-end numbers of a window, and what an earlier line
    reports beside them (how late the generator ran, speculation)."""
    ttft, itl, tokens, failed = [], [], 0, 0
    for i in w.due_in:
        times = w.rec.times.get(w.rid_of.get(i), [])
        if times:
            ttft.append(times[0] - w.due(i))
        else:
            failed += 1
    for times in w.rec.times.values():
        tokens += sum(1 for t in times if w.t_open <= t < w.t_close)
        itl.extend(b - a for a, b in zip(times, times[1:])
                   if w.t_open <= b < w.t_close)
    late = [w.submit_at[i] - w.due(i) for i in w.due_in]
    # due by the close and not yet answered: a queue that grows over the
    # window is past the knee
    waiting = sum(1 for i in w.submit_at if w.due(i) < w.t_close and not
                  w.rec.times.get(w.rid_of.get(i), [w.t_close])[0] < w.t_close)
    mt = engine.metrics
    offered = sum(w.reqs[i].max_new for i in w.due_in) / w.seconds
    return {
        "metrics": {"serve_tok_s": tokens / w.seconds,
                    "ttft_p95_ms": 1e3 * percentile(ttft, 95),
                    "itl_p95_ms": 1e3 * percentile(itl, 95)},
        "failed": failed,
        "notes": {
            "generator_late_ms": {"p50": 1e3 * percentile(late, 50),
                                  "p95": 1e3 * percentile(late, 95),
                                  "max": 1e3 * max(late, default=0.0)},
            "offered_tok_s": offered,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "itl_p50_ms": 1e3 * percentile(itl, 50),
            "spec_accept_rate": (mt.accepted_tokens / mt.drafted_tokens
                                 if mt.drafted_tokens else None),
            "drafted_tokens": mt.drafted_tokens,
            "drafted_per_decoded": (mt.drafted_tokens / mt.decode_tokens
                                    if mt.decode_tokens else None),
            "waiting_at_close": waiting,
            "accepted_tokens": mt.accepted_tokens,
            "preemptions": mt.preemptions, "rejected": len(w.rejected),
            "traced_in_window": w.compiles,
            "requests_due_in_window": len(w.due_in),
            "tokens_in_window": tokens}}


def run(cell, seed: int, seconds: float, trace: bool, root: Path,
        t_start: float, control: bool = False) -> dict:
    import jax
    cfg, mix = cell.config, cell.traffic
    session, engine = build(cfg, seed)
    vocab = session.model.vocab_size
    warm_up(engine, mix, vocab, seed)
    reqs = traffic.generate(mix, seconds, seed, vocab)
    trace_dir = root / ".bench_trace" / cell.name if trace else None
    w = open_loop(engine, mix, reqs, seconds, trace_dir)
    summ = summarize(w, engine)
    harness.note(dict(summ["metrics"], **summ["notes"]))
    metrics = dict(summ["metrics"], setup_s=w.t_open - t_start)
    device = harness.device_info(jax.devices()[:cell.chips])

    layer_ctx = None
    if trace:
        from bench import trace as tr_mod
        tr = tr_mod.read(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr_mod.busy_s(tr)
        device["window_s"] = tr_mod.window_s(tr)
        layer_ctx = serve_layer_ctx(cell, tr, w, engine, device)
        harness.note({"device_s_by_program": tr_mod.top_modules(tr)})

    # correctness: free the program's state, then the reference
    served = {i: list(engine.results[w.rid_of[i]]) for i in w.due_in
              if reqs[i].greedy and w.rid_of.get(i) in engine.results}
    max_seq = engine.cfg.max_seq_len
    del engine, session
    gc.collect()
    checks, control_gap, got = check(cfg, mix, reqs, served, seed, max_seq,
                                     control)
    harness.note({"readings": got})
    out = {"attempted": len(w.due_in),
           "failed": summ["failed"],
           "metrics": metrics, "device": device, "checks": checks,
           "layer_ctx": layer_ctx}
    if control:
        out["control"] = control_gap
    return out


def sample_for_check(reqs, served: dict, seed: int):
    """A seeded sample of the finished greedy requests, the longest among
    them."""
    ids = sorted(served)
    if not ids:
        return []
    longest = max(ids, key=lambda i: len(reqs[i].prompt) + len(served[i]))
    rest = [i for i in ids if i != longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.choice(len(rest), min(CHECK_SAMPLE, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def check(cfg, mix, reqs, served, seed, max_seq, control=False):
    """How far below the reference's best logit the served greedy tokens'
    logits lie, over the sample: the widest gap (``logit_gap``) and the
    mean (``mean_logit_gap``).  With ``control`` the same two numbers of
    the int8 control, read on the same positions, come back beside them.
    The configuration's ``limits`` name the numbers compared."""
    import jax
    import jax.numpy as jnp
    m = cfg["model"]
    sample = sample_for_check(reqs, served, seed)
    params = dense.make_params(m, seed) if sample else None
    out_max = mix["output_len"]["max"]
    fn = jax.jit(functools.partial(dense.served_gap, m),
                 static_argnames=("control",))
    gaps, ctrl_gaps = [], []
    for i in sample:
        prompt, out = reqs[i].prompt, served[i]
        seq = np.zeros(max_seq, np.int32)
        seq[:len(prompt) + len(out)] = prompt + out
        last = np.minimum(np.arange(out_max), len(out) - 1)
        gap, ctrl = fn(params, jnp.asarray(seq),
                       jnp.asarray(len(prompt) - 1 + last, np.int32),
                       jnp.asarray(np.asarray(out)[last], np.int32),
                       control=control)
        gaps.append(np.asarray(gap)[:len(out)])
        if control:
            ctrl_gaps.append(np.asarray(ctrl)[:len(out)])

    def readings(parts):
        if not parts:
            return {"logit_gap": float("inf"), "mean_logit_gap": float("inf")}
        x = np.concatenate(parts)
        return {"logit_gap": float(x.max()), "mean_logit_gap": float(x.mean())}

    got = readings(gaps)
    checks = checks_of(got, cfg["limits"], int(sum(len(g) for g in gaps)))
    return checks, (readings(ctrl_gaps) if control else None), got


def checks_of(readings: dict, limits: dict, checked_tokens: int) -> dict:
    """Each number the configuration's ``limits`` name beside its limit,
    and the count of tokens read."""
    checks = {k: {"value": readings[k], "limit": v} for k, v in limits.items()}
    checks["checked_tokens"] = {"value": checked_tokens, "limit": 1}
    return checks


def correct(checks: dict) -> bool:
    """Every gap at or under its limit, and some tokens checked."""
    return all((c["value"] >= c["limit"]) if name == "checked_tokens"
               else (c["value"] <= c["limit"])
               for name, c in checks.items())


def serve_layer_ctx(cell, tr, w: Window, engine, device: dict) -> dict:
    """What the serving readers take: the trace, the counter deltas over
    the traced window, and the prompts prefilled and tokens decoded that
    the host saw finish in it (prompt length, context length)."""
    before, after = w.counts["before"], w.counts["after"]
    lo, hi = before["t"], after["t"]
    prefills, decoded = [], []
    req_of = {rid: i for i, rid in w.rid_of.items()}
    for rid, times in w.rec.times.items():
        if rid not in req_of:
            continue
        n = len(w.reqs[req_of[rid]].prompt)
        for j, t in enumerate(times):
            if lo <= t < hi:
                (decoded.append(n + j) if j else prefills.append(n))
    return {"trace": tr,
            "counts": {k: after[k] - before[k] for k in before if k != "t"},
            "prefills": prefills, "decoded": decoded,
            "model": dense.dims(cell.config["model"]),
            "kv_bytes": 2,  # bfloat16 pages
            "decode_steps": engine.cfg.decode_steps,
            "peaks": harness.peaks_for(device["kind"],
                                       strict=device["platform"] == "tpu")}
