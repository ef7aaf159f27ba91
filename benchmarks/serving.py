"""Serving-engine benchmark: tokens/sec, TTFT, p50/p99 inter-token latency,
paged-vs-slotted KV-cache memory, and prefix-cache effectiveness.

    PYTHONPATH=src python benchmarks/serving.py [--arch qwen2.5-14b] \
        [--requests 16] [--batch 4] [--out BENCH_serving.json]
    PYTHONPATH=src python benchmarks/serving.py --smoke   # CI schema gate

Protocol: for each KV layout (paged, slotted) one warm-up pass populates
the jit caches (bucketed prefill + the single batched-decode executable),
then the measured pass serves a fresh queue of ragged-length requests
through the continuous-batching engine.  A third section serves a
shared-system-prompt workload (``--prefix-len`` common tokens + unique
tails) twice — prefix cache off ("cold") and on ("hit") — plus once on the
slotted pool, so the trajectory records the prefix cache's prefill-FLOPs
saving and any decode-throughput cost.  Results land in
``BENCH_serving.json`` so later PRs have a perf trajectory to beat.

Note on comparability: since the prefix-cache PR the paged measured pass
runs against pages cached by its own warm-up (realistic steady-state
traffic), so its ``prefill_tokens`` is far below the slotted section's;
``kv_bytes_saved_ratio`` is now peak-vs-peak (it used to divide the paged
peak by the slotted section's *static capacity*, mixing two protocols).
``compile_count`` is the engine-lifetime number of prefill traces —
bounded by the power-of-two bucketing, O(log max_seq_len).

Since the KV-layout PR the record also carries per-arch sections
(``record["archs"]``) for the newly paged families — deepseek-v2-lite
(MLA latent pages) and mixtral (ring-wrapped window pages) — each with the
same paged/slotted/prefix schema, so the layout seam's acceptance numbers
(paged peak below slotted, prefix_hit_rate) live in the trajectory.
Workload knobs are clamped per arch to its ``KVLayout`` (pages must tile
the attention window; sequences stay inside the window so the ring's lazy
growth can undercut the slotted pool's window-sized preallocation), and
serve capacity is provisioned one page above the workload maximum — the
"slotted pins the worst case, paged holds actuals" regime paging exists
for.

Since the observability PR every record also carries a ``phases`` section:
a separate *traced* pass (``ServeConfig(trace=True)`` — repro.obs spans,
unfenced) attributing the engine-cycle wall to host planning vs
dispatching prefill and decode vs waiting on the device at retire vs glue.
Traced numbers stay out of the throughput trajectory; they exist to
explain it — e.g. whether the paged-vs-slotted gap on ROADMAP open item 1
is host bookkeeping or device time.

Since the sampling/spec PR the record carries a ``spec`` section:
repetitive decode-dominated traffic (constant-token prompts drive the tiny
random models into argmax cycles the bigram drafter replays) served twice
— speculative decoding on and off, both at ``pipeline_depth=1`` — with
``accept_rate``, the spec-on/spec-off throughput ratio (``speedup``), and
output identity (spec only ever changes speed, never tokens).  ``--smoke``
gates on identity plus spec engagement, and on ``accept_rate > 0`` for the
headline arch.

Since the quantized-KV PR every pass summary is stamped with its
``kv_dtype`` and per-head paged archs carry a ``quantized`` section: the
measured workload re-served from int8 pages (per-row bf16 scales, dequant
fused into the paged-attention kernels) through both the jnp-oracle and
Pallas paths, with ``kv_bytes_peak_ratio`` vs the fp32 paged pass (smoke
gate <= 0.30x), ``same_budget_seq_ratio`` (>= 2x sequences admitted under
the same HBM budget) and kernel-on/off ``token_identical``.  MLA archs
omit the section (latent pages stay fp — the layout seam rejects int8).

Untraced passes *omit* the phase-derived keys entirely
(``repro.obs.TRACED_ONLY_KEYS``): with tracing off those fields were
emitted as literal ``0.0`` — reading as "zero host overhead" — so the
schema gate now asserts their absence instead of their presence.

``--smoke`` runs a seconds-scale workload *per smoke arch* (full, MLA and
windowed layouts) and asserts the emitted records still carry every
schema key, so drift breaks CI instead of the next PR's analysis; it also
writes one Perfetto-loadable Chrome trace per arch (``--trace-dir``) and
gates on trace-event schema validity, the pipeline span names
(``step.plan``/``step.submit``/``step.retire``), >= 95% phase coverage of
the engine-loop wall, and ``host_overhead_frac <= 0.25`` (the pipelined
submit/retire engine's bar — the synchronous loop sat at 0.37-0.49).
The ``run()`` hook returns harness-style ``(name, us_per_call, derived)``
rows.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

DEFAULTS = dict(arch="qwen2.5-14b", requests=16, batch=4, prompt_len=16,
                max_new=12, page_size=8, prefix_len=64)

#: per-arch sections recorded alongside the headline arch (the families
#: the KV-layout seam brought onto the paged pool)
BENCH_ARCHS = ("deepseek-v2-lite-16b", "mixtral-8x22b")
#: archs the CI smoke gate exercises (one per page layout)
SMOKE_ARCHS = ("qwen2.5-14b",) + BENCH_ARCHS

#: schema gate: every emitted record must carry these (CI --smoke asserts);
#: 'paged'/'prefix'/'spec' are required only for archs with a paged decode
#: path ('spec' additionally needs the spec_serve capability)
REQUIRED_KEYS = ("arch", "requests", "slotted", "kv_bytes_saved_ratio",
                 "prefix", "spec", "quantized", "phases")
REQUIRED_SUMMARY_KEYS = ("tokens_per_sec", "ttft_p50_s", "itl_p50_s",
                         "kv_bytes_peak", "kv_bytes_slotted",
                         "prefill_tokens", "prefix_hit_rate",
                         "prefill_tokens_saved", "compile_count",
                         "kv_dtype")
REQUIRED_PREFIX_KEYS = ("hit", "cold", "slotted_tokens_per_sec",
                        "prefill_tokens_saved_ratio", "token_identical")
#: speculative-decoding workload section (repetitive traffic, spec on/off)
REQUIRED_SPEC_KEYS = ("on", "off", "accept_rate", "speedup",
                      "token_identical")
#: int8 quantized-KV workload section (per-head paged layouts only — MLA
#: latent pages stay fp): the same measured workload served from int8
#: pages, its memory ratios against the fp32 paged pass, and the
#: kernel-on/off identity of the quantized path
REQUIRED_QUANT_KEYS = ("int8", "kv_bytes_peak_ratio", "page_bytes_ratio",
                       "same_budget_seq_ratio", "token_identical")
#: CI bars for the quantized section: an int8 page (int8 rows + bf16
#: scales) must hold the measured peak at <= 0.30x the fp32 paged pass
#: (~0.28 on the hd=16 smoke shapes), and the same HBM budget must admit
#: >= 2x the concurrent sequences
QUANT_PEAK_GATE = 0.30
QUANT_ADMIT_GATE = 2.0
#: per-arch traced-attribution section (repro.obs): where the cycle goes;
#: ``prefill_kernel`` records whether the Pallas paged kernels (decode +
#: chunked prefill + verify) drove the pass — backend-selected, so the
#: pass's device time (retire_wait_frac) is attributable to the right path
REQUIRED_PHASE_KEYS = ("step_time_s", "plan_frac", "prefill_dispatch_frac",
                       "decode_dispatch_frac", "retire_wait_frac",
                       "other_frac", "host_overhead_frac", "coverage",
                       "prefill_kernel")
#: CI bar for host glue between device calls on the traced smoke pass —
#: the number the pipelined submit/retire refactor drives down (was
#: 0.49/0.45/0.37 across the smoke archs on the synchronous engine)
HOST_OVERHEAD_GATE = 0.25
#: pipeline span names the emitted Chrome trace must carry (smoke gate):
#: a refactor that silently stops emitting the section spans would void
#: the attribution math without failing any numeric bar
REQUIRED_SPAN_NAMES = ("step", "step.plan", "step.submit", "step.retire")


def _arch_kw(arch, kw):
    """Clamp workload knobs to the arch's KVLayout: ring pages must tile
    the attention window, and sequences stay inside it so the ring's lazy
    growth (plus prefix sharing) can undercut the slotted pool."""
    from repro.configs import get_config
    from repro.models import registry

    layout = registry.build(get_config(arch, smoke=True)).kv_layout
    kw = dict(kw, arch=arch)
    if layout is not None and layout.window:
        w = layout.window
        kw["page_size"] = min(kw["page_size"], layout.max_page_size())
        kw["prompt_len"] = min(kw["prompt_len"], max(w // 2, 1))
        kw["max_new"] = min(kw["max_new"], max(w // 4, 1))
        kw["prefix_len"] = min(kw["prefix_len"], w)
    return kw


def _untraced(summary):
    """Strip phase-derived keys from an *untraced* pass's summary.  With
    tracing off per-phase time does not exist, so those fields are
    structurally ``0.0`` — emitting them into BENCH_serving.json read as
    "host overhead is zero" / "decode throughput is zero".  Only the
    traced attribution pass (:func:`_traced_attribution`) reports phase
    data; the schema gate asserts the absence."""
    from repro.obs import TRACED_ONLY_KEYS
    return {k: v for k, v in summary.items() if k not in TRACED_ONLY_KEYS}


def _make_engine(arch, batch, max_seq, max_new, kv_layout, page_size,
                 **serve_kw):
    from repro.configs import ServeConfig, get_config
    from repro.serving import ServingEngine

    cfg = get_config(arch, smoke=True)
    scfg = ServeConfig(max_batch=batch, max_queue=64, max_seq_len=max_seq,
                       max_new_tokens=max_new, max_prefills_per_step=2,
                       decode_steps=serve_kw.pop("decode_steps", 4),
                       kv_layout=kv_layout,
                       page_size=page_size, **serve_kw)
    return cfg, ServingEngine(cfg, scfg, seed=0)


def _serve_once(arch, requests, batch, prompt_len, max_new, kv_layout,
                page_size):
    import numpy as np

    # serve capacity one page above the workload maximum (real deployments
    # provision headroom; the slotted pool pins it, the paged pool holds
    # actual lengths — the gap is the paging win).  Page headroom beyond
    # the live worst case: refcount-0 cached pages survive between passes,
    # so the measured pass serves repeat traffic out of the prefix cache
    # (worst-case-only provisioning reclaims every cached page before its
    # prompt comes around again)
    max_seq = prompt_len + max_new + page_size
    pages = 3 * batch * (-(-max_seq // page_size)) + 1
    cfg, engine = _make_engine(arch, batch, max_seq, max_new,
                               kv_layout, page_size, num_pages=pages)
    rng = np.random.default_rng(0)
    lengths = rng.integers(max(prompt_len // 2, 1), prompt_len + 1,
                           size=requests)
    prompts = [rng.integers(0, cfg.vocab_size, (int(l),)) for l in lengths]
    # warm-up: compile the prefill buckets + the decode step (and, paged,
    # seed the prefix cache — the measured passes are steady-state traffic:
    # every block is already cached, so each pass repeats identical work)
    engine.generate(prompts, max_new)
    best = None
    for _ in range(5):                    # best-of-5: the box is shared
        engine.metrics.reset()
        engine.results.clear()
        out = engine.generate(prompts, max_new)
        assert len(out) == requests and all(len(t) == max_new for t in out)
        s = _untraced(engine.metrics.summary())
        s["compile_count"] = engine.prefill_compiles  # lifetime, not window
        s["kv_dtype"] = "fp32"          # the baseline passes store fp pages
        if best is None or s["tokens_per_sec"] > best["tokens_per_sec"]:
            best = s
    return engine.paged, best


def _traced_attribution(arch, requests, batch, prompt_len, max_new,
                        page_size, trace_path=None):
    """One *traced* pass (``ServeConfig(trace=True)``: repro.obs spans,
    unfenced): where the engine cycle's wall time goes — host planning vs
    dispatching prefill and decode vs waiting on the device at retire vs
    glue.

    Separate from the measured passes, so the attribution fractions never
    carry the ring's recording cost into the tokens_per_sec trajectory.
    Best-of-3 windows, same policy (and same reason) as the measured
    passes' best-of-5: the box is shared, and a scheduler interruption
    lands in host-attributed time, so the lowest-glue window is the
    closest to the engine's true overhead.
    When ``trace_path`` is set the Chrome trace JSON (Perfetto-loadable)
    of the last window is written there too."""
    import numpy as np
    from repro.obs import HOST_OVERHEAD_FRAC, phase_coverage

    max_seq = prompt_len + max_new + page_size
    pages = 3 * batch * (-(-max_seq // page_size)) + 1
    cfg, engine = _make_engine(arch, batch, max_seq, max_new, "auto",
                               page_size, num_pages=pages, trace=True)
    rng = np.random.default_rng(0)
    lengths = rng.integers(max(prompt_len // 2, 1), prompt_len + 1,
                           size=requests)
    prompts = [rng.integers(0, cfg.vocab_size, (int(l),)) for l in lengths]
    engine.generate(prompts, max_new)     # compile warm-up
    best = None
    for _ in range(3):
        engine.tracer.reset()             # measured traced window only
        engine.metrics.reset()
        engine.results.clear()
        engine.generate(prompts, max_new)
        s = engine.metrics.summary()
        st = s["step_time_s"] or 1.0
        out = {
            "step_time_s": s["step_time_s"],
            "plan_frac": s["plan_time_s"] / st,
            "prefill_dispatch_frac": s["prefill_dispatch_s"] / st,
            "decode_dispatch_frac": s["decode_dispatch_s"] / st,
            "retire_wait_frac": s["retire_wait_s"] / st,
            "other_frac": s["other_time_s"] / st,
            HOST_OVERHEAD_FRAC: s[HOST_OVERHEAD_FRAC],
            "coverage": phase_coverage(engine.tracer),
            "prefill_kernel": bool(engine.paged_kernel),
        }
        if best is None or out[HOST_OVERHEAD_FRAC] < best[HOST_OVERHEAD_FRAC]:
            best = out
    if trace_path:
        engine.save_trace(trace_path)
    return best


def _prefix_workload(arch, requests, batch, prefix_len, max_new, page_size):
    """Shared-system-prompt traffic: cold vs prefix-cache vs slotted.

    Runs prefill-dominated (short generation budget): the regime prefix
    caching targets — long shared prompts, few output tokens (RAG,
    classification, templated chat turns) — so the recorded throughput
    ordering reflects the prefill-FLOPs saving, not decode-kernel deltas.
    """
    import numpy as np

    max_new = min(max_new, 4)
    tail = max(prefix_len // 4, 4)
    max_seq = prefix_len + tail + max_new
    rng = np.random.default_rng(1)
    from repro.configs import get_config
    vocab = get_config(arch, smoke=True).vocab_size

    def workload(r):
        system = list(r.integers(0, vocab, (prefix_len,)))
        return [system + list(r.integers(0, vocab, (tail,)))
                for _ in range(requests)]

    prompts = workload(rng)
    # warm-up shares a *different* system prompt: compiles (miss + hit
    # buckets) land in the jit cache without seeding any block the measured
    # prompts could match, so the measured pass shows in-batch sharing only
    warm = workload(rng)

    def serve(kv_layout, **kw):
        """Warm-up pass, then best-of-5 measured passes (the box is shared;
        per-pass elapsed is seconds-scale and scheduler noise swings it
        30%+).  The prefix index is cleared before every measured pass, so
        each shows *in-batch* sharing only and all five are identical work
        — best-of is legitimate."""
        _, eng = _make_engine(arch, batch, max_seq, max_new, kv_layout,
                              page_size, **kw)
        eng.generate(warm, max_new)
        best = None
        for _ in range(5):
            if eng.paged:
                eng.pool.clear_prefix_cache()
            eng.metrics.reset()
            eng.results.clear()
            outs = eng.generate(prompts, max_new)
            s = _untraced(eng.metrics.summary())
            s["compile_count"] = eng.prefill_compiles
            if best is None or s["tokens_per_sec"] > best[1]["tokens_per_sec"]:
                best = (outs, s)
        return best

    out_hit, hit = serve("paged", enable_prefix_cache=True)
    out_cold, cold = serve("paged", enable_prefix_cache=False)
    _, slotted = serve("slotted")
    saved = 1.0 - hit["prefill_tokens"] / max(cold["prefill_tokens"], 1)
    return {
        "requests": requests, "prefix_len": prefix_len, "tail_len": tail,
        "hit": hit, "cold": cold,
        "slotted_tokens_per_sec": slotted["tokens_per_sec"],
        "prefill_tokens_saved_ratio": saved,
        "token_identical": out_hit == out_cold,
    }


def _spec_workload(arch, batch, page_size, spec_tokens=8, max_new=32,
                   passes=5):
    """Speculative decoding on repetitive decode-dominated traffic: the
    regime n-gram drafting targets (templated output, code, retrieval
    echoes — continuations the history already contains).

    Constant-token prompts push the tiny random models into short argmax
    cycles the bigram drafter replays, so acceptance is non-trivial and
    the recorded ``speedup`` (spec-on vs spec-off tokens/sec) reflects
    verify-one-forward replacing several decode dispatches.  Both arms run
    ``pipeline_depth=1``: at depth 2 a speculating slot alternates
    verify/idle cycles (the host needs the retired history to draft), so
    depth-1 isolates the drafting win from pipelining effects.  Both arms
    also run ``decode_steps=1`` and ``batch <= 2`` — the interactive
    low-ITL regime that speculation targets, where the baseline pays one
    dispatch per token.  At ``decode_steps=4`` the engine already
    amortises dispatches 4x inside the fused multi-step scan, and at
    high batch it amortises one decode dispatch across every slot while
    verify forwards run one per speculating slot (the classic
    spec-decode crossover: a win for interactive traffic, a wash or
    loss for saturated batch throughput) — in either regime the
    recorded ratio would measure dispatch amortisation, not drafting.
    Output identity between the arms is part of the record — spec only
    ever changes speed."""
    import numpy as np
    from repro.configs import get_config

    vocab = get_config(arch, smoke=True).vocab_size
    batch = min(batch, 2)
    requests = 2 * batch
    prompt_len = 16
    prompts = [[(1 + i) % vocab] * prompt_len for i in range(requests)]
    max_seq = prompt_len + max_new + page_size
    pages = 3 * batch * (-(-max_seq // page_size)) + 1

    def serve(enable):
        _, eng = _make_engine(arch, batch, max_seq, max_new, "paged",
                              page_size, num_pages=pages, pipeline_depth=1,
                              decode_steps=1, enable_spec=enable,
                              spec_tokens=spec_tokens)
        eng.generate(prompts, max_new)        # compile + cache warm-up
        best = None
        for _ in range(passes):
            eng.metrics.reset()
            eng.results.clear()
            outs = eng.generate(prompts, max_new)
            s = _untraced(eng.metrics.summary())
            if best is None or s["tokens_per_sec"] > best[1]["tokens_per_sec"]:
                best = (outs, s)
        return best

    out_on, on = serve(True)
    out_off, off = serve(False)
    return {
        "requests": requests, "prompt_len": prompt_len, "max_new": max_new,
        "spec_tokens": spec_tokens, "on": on, "off": off,
        "accept_rate": on["accept_rate"],
        "speedup": on["tokens_per_sec"] / max(off["tokens_per_sec"], 1e-9),
        "token_identical": out_on == out_off,
    }


def _quantized_workload(arch, requests, batch, prompt_len, max_new,
                        page_size, fp32_peak):
    """Int8 quantized-KV section: the measured workload re-served from
    int8 pages (per-row bf16 scales, dequant fused into the attention
    math), once through the jnp oracle path and once through the Pallas
    kernels (interpret off-TPU) — quantization is part of the written
    page, so the two must agree token for token.

    Memory evidence comes from the pool itself: ``kv_bytes_peak_ratio``
    divides the int8 pass's measured peak by the fp32 paged pass's
    (``fp32_peak``), ``page_bytes_ratio`` is the per-page storage ratio,
    and ``same_budget_seq_ratio`` is how many more worst-case sequences
    the same HBM byte budget admits — the oversubscription headroom
    quantization buys."""
    import numpy as np
    from repro.configs import get_config

    max_seq = prompt_len + max_new + page_size
    pages = 3 * batch * (-(-max_seq // page_size)) + 1
    # the exact workload _serve_once measured on the fp32 pools
    rng = np.random.default_rng(0)
    lengths = rng.integers(max(prompt_len // 2, 1), prompt_len + 1,
                           size=requests)
    vocab = get_config(arch, smoke=True).vocab_size
    prompts = [rng.integers(0, vocab, (int(l),)) for l in lengths]

    def serve(use_pallas):
        cfg, eng = _make_engine(arch, batch, max_seq, max_new, "paged",
                                page_size, num_pages=pages,
                                kv_dtype="int8", use_pallas=use_pallas)
        eng.generate(prompts, max_new)        # compile + cache warm-up
        best = None
        for _ in range(5):
            eng.metrics.reset()
            eng.results.clear()
            outs = eng.generate(prompts, max_new)
            s = _untraced(eng.metrics.summary())
            s["kv_dtype"] = "int8"
            if best is None or s["tokens_per_sec"] > best[1]["tokens_per_sec"]:
                best = (outs, s)
        return best + (eng.pool,)

    out_ref, int8, pool = serve(False)
    out_kern, int8_kern, _ = serve(True)
    # worst-case sequences one HBM byte budget admits, fp32 vs int8 pages:
    # both pools page identically (same table geometry), so the ratio is
    # pure bytes-per-page — measured off the live pool, not assumed
    budget = (pages - 1) * pool.page_bytes_fp32
    seq_pages = -(-max_seq // page_size)
    fp32_seqs = budget // (seq_pages * pool.page_bytes_fp32)
    int8_seqs = budget // (seq_pages * pool.page_bytes)
    return {
        "requests": requests, "prompt_len": prompt_len, "max_new": max_new,
        "int8": int8, "int8_kernel": int8_kern,
        "kv_bytes_peak_ratio": (int8["kv_bytes_peak"] / fp32_peak
                                if fp32_peak else 0.0),
        "page_bytes_ratio": pool.page_bytes / pool.page_bytes_fp32,
        "same_budget_seq_ratio": int8_seqs / max(fp32_seqs, 1),
        "token_identical": out_ref == out_kern,
    }


def _bench(trace_path=None, **kw):
    """{'paged': summary, 'slotted': summary, 'kv_bytes_saved_ratio': x,
    'prefix': {...}, 'spec': {...}, 'phases': {...}}.

    Archs without a paged decode path (recurrent families — no KVLayout)
    bench the slotted layout only: no 'paged'/'prefix'/'spec' section,
    ratio 0.  'phases' always runs (a separate traced pass — see
    ``_traced_attribution``)."""
    from repro.configs import get_config
    from repro.models import registry

    caps = registry.build(get_config(kw["arch"], smoke=True)).capabilities()
    paged_ok = "paged_serve" in caps
    record = {}
    for layout in (("paged", "slotted") if paged_ok else ("slotted",)):
        is_paged, s = _serve_once(kw["arch"], kw["requests"], kw["batch"],
                                  kw["prompt_len"], kw["max_new"],
                                  layout, kw["page_size"])
        assert is_paged == (layout == "paged")
        record[layout] = s
    record["kv_bytes_saved_ratio"] = 0.0
    record["prefix"] = {}
    if paged_ok:
        # peak-vs-peak: what the paged pool held at its high-water mark vs
        # what the slotted pool held at its (constant) one.  (The previous
        # formula divided the paged peak by the *slotted-equivalent
        # capacity* reported inside the paged section — a protocol mix
        # that understated the saving.)
        peak = record["paged"]["kv_bytes_peak"]
        wall = record["slotted"]["kv_bytes_peak"]
        record["kv_bytes_saved_ratio"] = (1.0 - peak / wall) if wall else 0.0
        record["prefix"] = _prefix_workload(
            kw["arch"], kw["requests"], kw["batch"], kw["prefix_len"],
            kw["max_new"], kw["page_size"])
    record["spec"] = {}
    if paged_ok and "spec_serve" in caps:
        record["spec"] = _spec_workload(kw["arch"], kw["batch"],
                                        kw["page_size"])
    # int8 quantized-KV section: per-head paged layouts only (MLA latent
    # pages stay fp — the layout seam rejects the combination)
    record["quantized"] = {}
    layout = registry.build(get_config(kw["arch"], smoke=True)).kv_layout
    if paged_ok and layout is not None and layout.name != "latent":
        record["quantized"] = _quantized_workload(
            kw["arch"], kw["requests"], kw["batch"], kw["prompt_len"],
            kw["max_new"], kw["page_size"],
            fp32_peak=record["paged"]["kv_bytes_peak"])
    record["phases"] = _traced_attribution(
        kw["arch"], kw["requests"], kw["batch"], kw["prompt_len"],
        kw["max_new"], kw["page_size"], trace_path=trace_path)
    return record


def check_schema(record):
    """Raise AssertionError when the emitted record drifts from the schema
    later analysis (and the acceptance trajectory) depends on.  Slotted-only
    archs (no paged decode path) legitimately omit 'paged' and carry an
    empty 'prefix' section.  Per-arch sections under 'archs' (the KV-layout
    families) carry the same schema recursively."""
    for k in REQUIRED_KEYS:
        assert k in record, f"BENCH_serving.json schema drift: missing {k!r}"
    assert ("paged" in record) == bool(record["prefix"]), \
        "schema drift: paged section and prefix workload must co-occur"
    from repro.obs import TRACED_ONLY_KEYS
    for section in ("paged", "slotted"):
        if record.get(section):
            for k in REQUIRED_SUMMARY_KEYS:
                assert k in record[section], \
                    f"schema drift: missing {section}.{k}"
            for k in TRACED_ONLY_KEYS:
                assert k not in record[section], \
                    f"schema drift: untraced {section}.{k} would read as " \
                    "a measured zero — phase data belongs to 'phases' only"
    if record.get("prefix"):
        for k in REQUIRED_PREFIX_KEYS:
            assert k in record["prefix"], f"schema drift: missing prefix.{k}"
    if record.get("spec"):
        for k in REQUIRED_SPEC_KEYS:
            assert k in record["spec"], f"schema drift: missing spec.{k}"
        assert "drafted_tokens" in record["spec"]["on"], \
            "schema drift: spec.on summary lost the drafted_tokens counter"
    if record.get("quantized"):
        for k in REQUIRED_QUANT_KEYS:
            assert k in record["quantized"], \
                f"schema drift: missing quantized.{k}"
        assert record["quantized"]["int8"].get("kv_dtype") == "int8", \
            "schema drift: quantized.int8 summary lost its kv_dtype stamp"
    for k in REQUIRED_PHASE_KEYS:
        assert k in record["phases"], f"schema drift: missing phases.{k}"
    for arch, sub in record.get("archs", {}).items():
        check_schema(sub)


def run(**overrides):
    """Harness hook: [(name, us_per_call, derived), ...]."""
    kw = {**DEFAULTS, **overrides}
    r = _bench(**kw)
    s = r["slotted"]
    p = r.get("paged", s)
    px = r.get("prefix") or {}
    return [
        ("serving_tokens_per_sec", 0.0, p["tokens_per_sec"]),
        ("serving_tokens_per_sec_slotted", 0.0, s["tokens_per_sec"]),
        ("serving_ttft_p50", p["ttft_p50_s"] * 1e6, p["ttft_p50_s"]),
        ("serving_ttft_p99", p["ttft_p99_s"] * 1e6, p["ttft_p99_s"]),
        ("serving_itl_p50", p["itl_p50_s"] * 1e6, p["itl_p50_s"]),
        ("serving_itl_p99", p["itl_p99_s"] * 1e6, p["itl_p99_s"]),
        ("serving_kv_bytes_peak_paged", 0.0, p["kv_bytes_peak"]),
        ("serving_kv_bytes_peak_slotted", 0.0, s["kv_bytes_peak"]),
        ("serving_kv_bytes_saved_ratio", 0.0, r["kv_bytes_saved_ratio"]),
        ("serving_prefix_hit_rate", 0.0,
         px.get("hit", {}).get("prefix_hit_rate", 0.0)),
        ("serving_prefill_tokens_saved_ratio", 0.0,
         px.get("prefill_tokens_saved_ratio", 0.0)),
        ("serving_spec_accept_rate", 0.0,
         (r.get("spec") or {}).get("accept_rate", 0.0)),
        ("serving_spec_speedup", 0.0,
         (r.get("spec") or {}).get("speedup", 0.0)),
        ("serving_int8_kv_peak_ratio", 0.0,
         (r.get("quantized") or {}).get("kv_bytes_peak_ratio", 0.0)),
        ("serving_int8_same_budget_seq_ratio", 0.0,
         (r.get("quantized") or {}).get("same_budget_seq_ratio", 0.0)),
        ("serving_prefill_compile_count", 0.0, p["compile_count"]),
        ("serving_plan_time_frac", 0.0, r["phases"]["plan_frac"]),
        ("serving_retire_wait_frac", 0.0,
         r["phases"]["retire_wait_frac"]),
        ("serving_host_overhead_frac", 0.0,
         r["phases"]["host_overhead_frac"]),
        ("serving_phase_coverage", 0.0, r["phases"]["coverage"]),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=DEFAULTS["arch"])
    ap.add_argument("--requests", type=int, default=DEFAULTS["requests"])
    ap.add_argument("--batch", type=int, default=DEFAULTS["batch"])
    ap.add_argument("--prompt-len", type=int, default=DEFAULTS["prompt_len"])
    ap.add_argument("--max-new", type=int, default=DEFAULTS["max_new"])
    ap.add_argument("--page-size", type=int, default=DEFAULTS["page_size"])
    ap.add_argument("--prefix-len", type=int, default=DEFAULTS["prefix_len"],
                    help="shared system-prompt length of the prefix-cache "
                         "workload section")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run + schema assertion (CI gate); "
                         "does not overwrite BENCH_serving.json")
    ap.add_argument("--trace-dir", default=".",
                    help="where --smoke writes its per-arch Chrome traces "
                         "(smoke_trace_<arch>.json, Perfetto-loadable; "
                         "CI uploads them as artifacts)")
    ap.add_argument("--out", default=str(Path(__file__).resolve().parents[1]
                                         / "BENCH_serving.json"))
    args = ap.parse_args()
    kw = dict(arch=args.arch, requests=args.requests, batch=args.batch,
              prompt_len=args.prompt_len, max_new=args.max_new,
              page_size=args.page_size, prefix_len=args.prefix_len)
    if args.smoke:
        kw.update(requests=6, batch=2, prompt_len=8, max_new=4,
                  page_size=4, prefix_len=16)
        # one workload per page layout: full (contiguous k/v), MLA
        # (latent), windowed (ring) — schema asserted for each, plus the
        # trace gate: the emitted Chrome trace must be schema-valid
        # (every event carries ph/ts/pid/tid) and the engine-track section
        # spans must cover >= 95% of the step wall (the attribution bar)
        Path(args.trace_dir).mkdir(parents=True, exist_ok=True)
        for arch in SMOKE_ARCHS:
            akw = _arch_kw(arch, kw)
            tp = Path(args.trace_dir) / f"smoke_trace_{arch}.json"
            r = _bench(trace_path=str(tp), **akw)
            record = {"arch": arch, "requests": akw["requests"], **r}
            check_schema(record)
            evs = json.loads(tp.read_text())["traceEvents"]
            assert evs and all({"ph", "ts", "pid", "tid"} <= set(e)
                               for e in evs), \
                f"trace schema drift in {tp}"
            names = {e["name"] for e in evs}
            missing = [n for n in REQUIRED_SPAN_NAMES if n not in names]
            assert not missing, \
                f"trace span drift in {tp}: missing {missing} — the " \
                "pipeline sections stopped being traced"
            ph = record["phases"]
            assert ph["coverage"] >= 0.95, \
                f"phase spans cover {ph['coverage']:.1%} < 95% of the " \
                f"engine-loop wall [{arch}]"
            assert ph["host_overhead_frac"] <= HOST_OVERHEAD_GATE, \
                f"host_overhead_frac={ph['host_overhead_frac']:.2f} > " \
                f"{HOST_OVERHEAD_GATE} [{arch}]: host glue between device " \
                "calls regressed past the pipelined-engine bar"
            sp = record["spec"]
            if sp:
                assert sp["token_identical"], \
                    f"spec changed tokens [{arch}] — verification must " \
                    "replay the engine's own sampler exactly"
                assert sp["on"]["drafted_tokens"] > 0, \
                    f"spec never engaged on the repetitive workload [{arch}]"
                if arch == SMOKE_ARCHS[0]:
                    assert sp["accept_rate"] > 0, \
                        "spec accepted nothing on the repetitive workload " \
                        f"[{arch}] — verify/accept plumbing is broken"
                    # headline arch must actually profit: one batched
                    # verify emitting ~accept_rate * spec_tokens tokens
                    # has to beat per-token decode dispatches.  Ring
                    # archs clamp drafts to 1 (cell aliasing) and are
                    # recorded but not gated.
                    assert sp["speedup"] >= 1.0, \
                        f"spec-on slower than spec-off [{arch}]: " \
                        f"{sp['speedup']:.2f}x on the repetitive workload"
            qz = record["quantized"]
            if qz:
                assert qz["token_identical"], \
                    f"int8 kernel-on vs kernel-off token drift [{arch}] — " \
                    "fused dequant diverged from the jnp oracle"
                assert qz["kv_bytes_peak_ratio"] <= QUANT_PEAK_GATE, \
                    f"int8 kv_bytes_peak at " \
                    f"{qz['kv_bytes_peak_ratio']:.3f}x fp32 > " \
                    f"{QUANT_PEAK_GATE} [{arch}] — the quantized page " \
                    "layout stopped paying for itself"
                assert qz["same_budget_seq_ratio"] >= QUANT_ADMIT_GATE, \
                    f"int8 admits only {qz['same_budget_seq_ratio']:.1f}x " \
                    f"sequences under the fp32 byte budget [{arch}] " \
                    f"(gate {QUANT_ADMIT_GATE}x)"
            hit = (record["prefix"] or {}).get("hit", {})
            print(f"smoke OK [{arch}]: schema intact; "
                  f"prefix_hit_rate={hit.get('prefix_hit_rate', 0.0):.2f} "
                  f"kv_saved={record['kv_bytes_saved_ratio']:.2f} "
                  f"phase_coverage={ph['coverage']:.2f} "
                  f"retire_wait_frac={ph['retire_wait_frac']:.2f} "
                  f"prefill_kernel={ph['prefill_kernel']} "
                  f"host_overhead={ph['host_overhead_frac']:.2f} "
                  f"accept_rate={(sp or {}).get('accept_rate', 0.0):.2f} "
                  f"spec_speedup={(sp or {}).get('speedup', 0.0):.2f} "
                  f"int8_peak_ratio="
                  f"{(qz or {}).get('kv_bytes_peak_ratio', 0.0):.2f} "
                  f"int8_admits="
                  f"{(qz or {}).get('same_budget_seq_ratio', 0.0):.1f}x "
                  f"(trace: {tp})")
        return
    record = {
        "arch": kw["arch"], "smoke": True, "requests": kw["requests"],
        "batch_slots": kw["batch"], "prompt_len": kw["prompt_len"],
        "max_new": kw["max_new"], "page_size": kw["page_size"],
        **_bench(**kw),
    }
    # per-arch sections for the KV-layout families (latent + ring pages)
    record["archs"] = {}
    for arch in BENCH_ARCHS:
        if arch == kw["arch"]:
            continue
        akw = _arch_kw(arch, kw)
        sub = _bench(**akw)
        record["archs"][arch] = {
            "arch": arch, "requests": akw["requests"],
            "prompt_len": akw["prompt_len"], "max_new": akw["max_new"],
            "page_size": akw["page_size"], **sub,
        }
    check_schema(record)
    Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
    print(json.dumps(record, indent=2))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
