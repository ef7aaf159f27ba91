"""Continuous-batching serving engine with a pipelined submit/retire cycle.

The user supplies a model config (whose registry bundle declares the
``ServeContract`` / ``PagedServeContract`` / ``PagedPrefillContract`` decode
paths — the engine dispatches on ``bundle.capabilities()``, never on
``is None`` probes); the engine supplies everything the paper's transparency
principle says the runtime should own: request admission, slot-level
KV-cache management, prefix-cache page sharing, prefill/decode interleaving,
and mesh sharding.  A sequential "one request at a time" mental model in,
heavy traffic out.  User scripts reach this through ``repro.api``'s
``Session.serve`` / ``Session.generate``.

Pipelined event loop (one ``step()`` = one cycle, three phases):

  plan    — pure host decisions, nothing blocks on the device: priority
            preemption, admission (prefix-page mapping / slot+page
            allocation), chunk sizing + page preparation, per-slot decode
            budgets (``limits``), and lazy page growth for the whole
            decode span.  Produces an immutable ``_StepPlan``.
  submit  — dispatch the plan to the device: whole-prompt prefills +
            state scatters, suffix chunks, then ONE fused decode scan
            covering all ``decode_steps`` for every decodable slot
            (``lax.scan`` — one dispatch per cycle instead of one per
            token).  Host positions advance immediately (deterministic
            once planned), so the *next* plan can run while this step is
            still executing.
  retire  — materialise the *previous* cycle's results (the only host <-
            device sync, traced or not) and emit its tokens in the
            exact order the synchronous engine would have: admission
            first-tokens, chunk-completion first-tokens, then decode rows
            step-major / slot-minor.  Completion, EOS cuts, and stream
            callbacks all happen here.

With ``pipeline_depth=2`` (default) step N+1 plans and submits while step
N's device work is in flight and retires N afterwards — the device never
waits for host planning, and the host never blocks mid-cycle.
``pipeline_depth=1`` retires each cycle immediately after submitting it
(the synchronous escape hatch, ``--sync`` on the launchers).  Token
identity is the non-negotiable gate: per-slot decode budgets are computed
exactly (pending in-flight emissions are subtracted from the remaining
token budget), speculative rows past an EOS cut are dropped at retire,
and a preempted victim's in-flight tokens still emit before it can be
re-admitted — async output matches the depth-1 engine token for token.

Prefill compiles are bounded: prompt/chunk lengths are padded to power-of-
two buckets with masked tails (``ServeConfig.prefill_bucket``), so the jit
cache holds O(log max_seq_len) entries instead of one per distinct prompt
length (``metrics.compile_count`` tracks traces).  Recurrent families
(whose state a masked tail would corrupt) keep exact-length prefills.

KV memory is page-granular for every family with a ``KVLayout``
(``repro.serving.layouts``); pages are allocated lazily as positions cross
page boundaries — for the fused scan the whole ``limits[slot]``-long span
is prepared up front (``ensure_decode_capacity(steps=...)``), ring cells
rotating / copy-on-writing before the dispatch so the scan writes only
into private prepared pages.  On page pressure the engine preempts the
youngest lowest-priority request (resume re-prefills; emitted tokens are
kept, so greedy output is unchanged).  Recurrent families (RG-LRU / RWKV:
O(1) state per slot — nothing to page) fall back to the slotted pool;
``ServeConfig.kv_layout`` forces either layout.

Decoding is greedy (argmax) by default and per-request sampled on demand
(``submit(..., sampling=SamplingParams(...))``): the sampler keys a
counter-based PRNG by (request seed, absolute token index) — see
``repro.serving.sampling`` — so batched serving stays *token-identical*
to an unbatched sequential decode of each request whatever the batch
composition, slot assignment, KV layout, mesh or pipeline depth: the
serving analogue of the paper's Fig. 7 equivalence claim (tested in
tests/test_serving.py, tests/test_prefix_cache.py, tests/test_sampling.py
and, for the pipeline itself, tests/test_pipeline.py).  Temperature 0 is
lowered to argmax, so all-greedy traffic dispatches the exact greedy scan
(byte-identical tokens, no extra compiles).  The constructor's ``seed``
initialises *parameters* only (when ``params`` is None) — sampling seeds
are strictly per-request, never global engine state.

Speculative decoding (``ServeConfig.enable_spec``, paged layouts with a
``PagedVerifyContract``): a host-side n-gram drafter proposes up to
``spec_tokens`` continuations per eligible slot; submit runs ONE verify
forward over [last token, drafts] (prefill-style scatter, so accepted KV
lands directly in the slot's pages); retire accepts the longest prefix of
drafts that deterministically replays what the non-speculative engine
would have emitted, rewinds the slot past the first mismatch and emits
accepted tokens + the correction token.  Because verification replays the
exact sampler (argmax when greedy), spec-on output is token-identical to
spec-off — speculation only changes *when* tokens are computed, never
*which*.

Mesh transparency: pass a ``MeshConfig`` and the engine places parameters
via the same logical-axis rules as ``TransparentTrainer`` (tensor-parallel
decode over "model") and shards the slot pool over the data axes
(data-parallel replica serving).  No user code changes — the config *is*
the deployment.
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MeshConfig, ModelConfig, ServeConfig
from repro.models import common, registry
from repro.obs import (INFLIGHT_COUNTER, NULL_TRACER, Tracer, request_track,
                       write_chrome_trace)
from repro.serving.kvcache import SlotKVCachePool
from repro.serving.layouts import quantized_layout
from repro.serving.metrics import ServingMetrics
from repro.serving.paged import PagedKVCachePool
from repro.serving.sampling import (GREEDY, PACKED_WIDTH, SamplingParams,
                                    pack_params, sample_tokens)
from repro.serving.scheduler import Request, Scheduler
from repro.serving.spec import DrafterPool

P = jax.sharding.PartitionSpec

# stream callback: (request_id, token, done) -> None
StreamFn = Callable[[int, int, bool], None]

#: smallest prefill bucket — below this the pad overhead beats the compile
_MIN_BUCKET = 16


def bucket_len(n: int, cap: int, *, floor: int = _MIN_BUCKET) -> int:
    """Smallest power-of-two >= n (floored at ``floor``), clamped to the
    cache capacity ``cap`` — the final bucket is the capacity itself, so
    every admissible length lands in O(log cap) distinct shapes."""
    assert 1 <= n <= cap, (n, cap)
    return min(max(floor, 1 << (n - 1).bit_length()), cap)


class _PrefillJob:
    """Host-side progress of one request's chunked suffix prefill."""

    __slots__ = ("req", "prompt", "done")

    def __init__(self, req: Request, prompt: Tuple[int, ...], done: int):
        self.req = req
        self.prompt = prompt
        self.done = done                  # tokens already cached


class _AdmitPlan:
    """A planned whole-prompt admission (slot already allocated).

    ``cached_tok`` set means the full-hit fast path: every prompt block is
    mapped from the prefix cache AND the pool remembered the greedy next
    token, so submit dispatches nothing for this admission — it just seeds
    the device token chain and the slot decodes this same cycle."""

    __slots__ = ("req", "slot", "prompt", "cached_tok")

    def __init__(self, req: Request, slot: int, prompt: Tuple[int, ...],
                 cached_tok=None):
        self.req = req
        self.slot = slot
        self.prompt = prompt
        self.cached_tok = cached_tok


class _ChunkPlan:
    """One planned suffix chunk (pages already prepared)."""

    __slots__ = ("job", "slot", "start", "chunk", "completes")

    def __init__(self, job: _PrefillJob, slot: int, start: int, chunk: int,
                 completes: bool):
        self.job = job
        self.slot = slot
        self.start = start
        self.chunk = chunk
        self.completes = completes


class _SpecPlan:
    """One planned speculative verify: a slot whose decode row is swapped
    for a single drafted-token verification forward this cycle."""

    __slots__ = ("req", "slot", "drafts", "start", "m")

    def __init__(self, req: Request, slot: int, drafts: Tuple[int, ...],
                 start: int, m: int):
        self.req = req
        self.slot = slot
        self.drafts = drafts              # m drafted token ids
        self.start = start                # pool pos = index of last token
        self.m = m


class _StepPlan:
    """Output of the plan phase: everything submit dispatches.  The draft
    phase (``_plan_spec``) may swap decode rows for ``specs`` entries
    before submit; after that the plan is frozen."""

    __slots__ = ("admits", "chunks", "rows", "limits", "mask", "specs")

    def __init__(self, admits, chunks, rows, limits, mask):
        self.admits: List[_AdmitPlan] = admits
        self.chunks: List[_ChunkPlan] = chunks
        self.rows: List[Tuple[int, int]] = rows      # (slot, rid), decodable
        self.limits: Dict[int, int] = limits         # slot -> decode budget
        self.mask: Tuple[int, ...] = mask            # slots masked to trash
        self.specs: List[_SpecPlan] = []             # draft-phase verify jobs


class _InFlight:
    """One submitted-but-not-retired cycle: device handles + emission order.

    ``overrides`` are the prefill-origin first tokens (device scalars —
    forcing them keeps the host out of the token chain), in the exact order
    the synchronous engine would have emitted them; ``stack`` is the decode
    scan's [decode_steps, slots] token matrix, read row-by-row at retire;
    ``specs`` holds the cycle's speculative verifies as one batched
    triple (plans, emit [N, width], nacc [N]) — all slots share a single
    device dispatch and a single host sync at retire.
    """

    __slots__ = ("overrides", "rows", "limits", "stack", "n_steps", "specs")

    def __init__(self, overrides, rows, limits, stack, n_steps, specs=None):
        self.overrides: List[Tuple[int, int, jax.Array]] = overrides
        self.rows: List[Tuple[int, int]] = rows
        self.limits: Dict[int, int] = limits
        self.stack = stack                           # device [n_steps, slots]
        self.n_steps = n_steps
        self.specs: Optional[Tuple[List[_SpecPlan], jax.Array, jax.Array]] = \
            specs


class ServingEngine:
    def __init__(self, model_cfg: ModelConfig,
                 serve_cfg: Optional[ServeConfig] = None, *,
                 params=None, mesh_cfg: Optional[MeshConfig] = None,
                 seed: int = 0, clock=None):
        self.model_cfg = model_cfg
        # (ServeConfig self-validates at construction — no re-check here)
        self.cfg = serve_cfg or ServeConfig()
        # observability: one engine-owned Tracer (ServeConfig(trace=True))
        # threaded through scheduler, pool and metrics; NULL_TRACER records
        # only while a profiler session runs (one is_enabled check per emit
        # otherwise).  Nothing is fenced either way.  The injectable clock
        # is shared with metrics so deterministic tests see one consistent
        # timeline across both.
        self.tracer = (Tracer(clock=clock, capacity=self.cfg.trace_capacity,
                              meta={"model": model_cfg.name,
                                    "family": model_cfg.family,
                                    "backend": jax.default_backend()})
                       if self.cfg.trace else NULL_TRACER)
        self.bundle = registry.build(model_cfg)
        caps = self.bundle.capabilities()
        if "serve" not in caps:
            raise ValueError(
                f"{model_cfg.name} ({model_cfg.family}) has no serving "
                "decode-path contract (ServeContract); encdec/vlm "
                "frontends need per-request modality inputs — see ROADMAP")

        # -- mesh placement (config-selected, transparent to callers) -----
        self.mesh = None
        dp_axes, dp_total, model_size = (), 1, 1
        if mesh_cfg is not None:
            from repro.launch import mesh as mesh_mod
            mesh_cfg.validate()
            self.mesh = mesh_mod.build_mesh(mesh_cfg)
            dp_axes = mesh_cfg.dp_axes
            dp_total = mesh_mod.dp_size(mesh_cfg)
            model_size = mesh_mod.model_size(mesh_cfg)
            rules = common.rules_for(mesh_cfg, model_cfg)
            param_sh = common.logical_to_mesh(self.bundle.specs, self.mesh,
                                              rules)
        if params is None:
            # ``seed`` initialises parameters ONLY.  Sampling randomness is
            # strictly per-request (SamplingParams.seed + absolute token
            # index) — engine-level state never leaks into token draws.
            params = self.bundle.init_params(jax.random.PRNGKey(seed))
        if self.mesh is not None:
            params = jax.device_put(params, param_sh)
        self.params = params

        # -- KV pool: page-granular when the family has a KVLayout (the
        # layout seam is the capability authority: per-head k/v, latent, or
        # ring-wrapped window pages; recurrent families' O(1) state has no
        # layout and stays slot-granular)
        self.layout = self.bundle.kv_layout
        self.paged = ("paged_serve" in caps
                      and self.cfg.kv_layout != "slotted")
        if self.cfg.kv_layout == "paged" and not self.paged:
            raise ValueError(
                f"{model_cfg.name} ({model_cfg.family}/{model_cfg.attn_kind})"
                " has no paged decode path (PagedServeContract / KVLayout); "
                "recurrent families' O(1) state uses the slotted pool "
                "(kv_layout='auto')")
        # prefix-cache page sharing + chunked prefill need the paged
        # prefill contract (engine writes pages in place, no state scatter)
        self._prefix_path = self.paged and "prefix_serve" in caps
        # speculative decoding needs the all-position verify head
        # (PagedVerifyContract -> "spec_serve"); ServeConfig.enable_spec
        # gates it per deployment, slotted layouts have no page rewind
        self._spec_path = (self.paged and "spec_serve" in caps
                           and self.cfg.enable_spec)
        # masked-tail power-of-two bucketing of whole-prompt prefills
        self._bucket_slotted = (self.cfg.prefill_bucket
                                and "bucketed_prefill" in caps)
        if self.paged:
            # windowed families: a page must fit (and tile) the window —
            # fail here with one ServeConfig-level error, not deep in the
            # pool or a kernel
            self.cfg.check_window(self.layout.window)
            # quantized pools: rewrite the layout's page leaves to int8 +
            # per-(page, offset, kv-head) fp32 scales.  Same single-error
            # discipline as check_window — int8 + MLA latents (or a family
            # that resolved slotted) fails here naming both knobs, not deep
            # in a kernel
            if self.cfg.kv_dtype != "fp32":
                self.cfg.check_kv_dtype(self.layout)
                self.layout = quantized_layout(self.layout,
                                               self.cfg.kv_dtype)
            self.pool = PagedKVCachePool(
                self.cfg.max_batch, self.cfg.page_size, self.cfg.max_seq_len,
                lambda: self.bundle.init_decode_state(1, self.cfg.page_size),
                num_pages=self.cfg.num_pages, mesh=self.mesh,
                model_size=model_size, layout=self.layout,
                enable_prefix_cache=(self.cfg.enable_prefix_cache
                                     and self._prefix_path),
                tracer=self.tracer)
            self._cache_len = self.pool.padded_len   # page-multiple prefill
            # ring chunks are capped at the window: a longer write-then-
            # attend chunk would wrap onto cells its own queries still need
            self._chunk_cap = self.layout.max_chunk_tokens(
                self.pool.padded_len)
            # the fused scan has the same wrap hazard: its span may not
            # exceed the window (contiguous layouts are unconstrained)
            self._span_cap = self.layout.max_decode_span(self.cfg.decode_steps)
        else:
            if self.cfg.kv_dtype != "fp32":
                # auto-resolved slotted (no KVLayout): same error the
                # explicit kv_layout='slotted' combination gets in validate()
                self.cfg.check_kv_dtype(None)
            self.pool = SlotKVCachePool(
                self.cfg.max_batch,
                lambda: self.bundle.init_decode_state(1, self.cfg.max_seq_len),
                mesh=self.mesh, dp_axes=dp_axes, dp_total=dp_total,
                model_size=model_size)
            self._cache_len = self.cfg.max_seq_len
            self._span_cap = self.cfg.decode_steps

        self.scheduler = Scheduler(self.cfg, tracer=self.tracer)
        self.metrics = ServingMetrics(clock, tracer=self.tracer)
        self.requests: Dict[int, Request] = {}
        self.results: Dict[int, List[int]] = {}
        self._rid = itertools.count()
        self._prefilling: Dict[int, _PrefillJob] = {}   # slot -> job
        # pipeline state: the one submitted-but-not-retired cycle, the
        # per-request count of its not-yet-emitted tokens (subtracted from
        # decode budgets so the pipeline never over-generates), and the
        # device-resident last token per slot (decode feeds decode without
        # a host round-trip; prefill logits override via one jitted setter)
        self._inflight: Optional[_InFlight] = None
        self._pending: Dict[int, int] = {}              # rid -> tokens in flight
        self._last_toks_dev = jnp.zeros((self.cfg.max_batch,), jnp.int32)
        # decode dispatches that took the sampled scan, and those of them
        # in which some row's top_k > 0 runs the sampler's top-k search:
        # the tracer's sampler.* counters
        self._sampled_dispatches = 0
        self._topk_dispatches = 0
        self.prefill_compiles = 0         # lifetime (metrics.reset survives)
        # speculative decoding: per-request n-gram drafters plus the slots
        # whose verify is in flight (those slots must not decode, draft
        # again, or be preempted until the verify retires)
        self._drafters = DrafterPool()
        self._spec_wait: set = set()
        # host mirror of each slot's next sampling index (= prompt+tokens
        # length); the slotted sampled scan needs it as an operand (the
        # paged pool carries pos on-device already)
        self._slot_pos = np.zeros((self.cfg.max_batch,), np.int64)

        # -- compiled entry points -----------------------------------------
        # prefill compiles are counted at trace time: a wrapper bump runs
        # once per new jit cache entry, which is exactly the XLA compile
        # count the bucketing is there to bound
        def _counted(fn):
            def wrapped(*a, **k):
                self.prefill_compiles += 1
                self.metrics.record_prefill_compile()
                # a[1] is the token operand: its (traced) shape is the
                # bucket this compile covers
                self.tracer.instant("prefill.compile",
                                    shape=list(a[1].shape))
                return fn(*a, **k)
            return wrapped

        # whole-prompt prefill: one jit object; XLA caches per
        # (bucket_len | prompt_len, cache_len) pair
        self._prefill = jax.jit(_counted(self.bundle.serve_prefill_fn),
                                static_argnames=("cache_len",))
        # tiny helpers keeping the token chain on-device: force a slot's
        # next token from prefill logits / read the greedy argmax — each
        # compiles once
        self._argmax1 = jax.jit(
            lambda logits: jnp.argmax(logits[0]).astype(jnp.int32))
        # sampled sibling of _argmax1: draw the prefill-origin first token
        # with the request's packed params at its absolute index
        self._sample1 = jax.jit(
            lambda logits, packed, idx: sample_tokens(
                logits, packed[None, :], idx[None])[0])
        self._set_tok = jax.jit(
            lambda toks, slot, tok: toks.at[slot].set(tok))

        decode_fn = self.bundle.decode_fn
        paged_decode_fn = self.bundle.paged_decode_fn
        paged_prefill_fn = self.bundle.paged_prefill_fn
        paged_verify_fn = self.bundle.paged_verify_fn
        n_steps = self.cfg.decode_steps
        spec_width = self.cfg.spec_tokens + 1   # last token + drafts

        # backend-selected like core/allreduce: the Pallas paged-attention
        # kernels on one TPU device (HBM traffic ~ pages held), traced ref
        # gather on CPU.  ServeConfig.use_pallas overrides (off-TPU the
        # kernels run in interpret mode — the ops wrappers select it
        # automatically), so tests/CI exercise the kernel paths everywhere.
        # Applies to every paged dispatch: decode scans, prefill chunks, and
        # spec-verify.  A compiled Mosaic kernel cannot be partitioned by
        # GSPMD (it would need a shard_map around each call), so a mesh of
        # several TPU devices serves through the gather path.
        on_tpu = jax.default_backend() == "tpu"
        n_devices = 1 if self.mesh is None else self.mesh.size
        if self.cfg.use_pallas is None:
            paged_kernel = on_tpu and n_devices == 1
        else:
            paged_kernel = self.cfg.use_pallas
            if paged_kernel and on_tpu and n_devices > 1 and self.paged:
                raise ValueError(
                    f"use_pallas=True on a {n_devices}-device TPU mesh: the "
                    "compiled paged kernels run on one device only — "
                    "leave use_pallas=None (auto) or serve on one device")
        self.paged_kernel = paged_kernel and self.paged

        # One fused dispatch per cycle: lax.scan over decode_steps.  Each
        # slot decodes exactly ``limits[slot]`` tokens; past its budget the
        # carry freezes — the frozen iterations idempotently replay the
        # last in-budget step (same token, same prepared position, same
        # deterministic K/V write), so no slot writes past its span and
        # the stacked output rows past the budget are ignored at retire.
        # ``last`` (the next cycle's token chain) is ``stack[-1]`` for any
        # slot that decoded at all: the frozen replays re-emit the last
        # in-budget token, so the final stack row IS ``stack[limit-1]``.
        # A budget below n_steps does NOT mean the request completes —
        # ``safe_decode_span`` caps continuing ring slots too — so the
        # chain must stay live; only limit-0 slots keep their input token.
        # ``packed`` is the pool's fused [slots, width+2] operand — page
        # table | pos | limits in one upload (see decode_operands); the
        # slices below are free under jit
        def _decode_scan_paged(params, toks0, pages, packed):
            table = packed[:, :-2]
            pos0 = packed[:, -2]
            limits = packed[:, -1]

            def body(carry, k):
                toks, pos, pages = carry
                logits, pages = paged_decode_fn(
                    params, toks[:, None],
                    {"pages": pages, "page_table": table, "pos": pos},
                    use_pallas=paged_kernel)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                adv = (k + 1) < limits
                return ((jnp.where(adv, nxt, toks),
                         jnp.where(adv, pos + 1, pos), pages), nxt)
            (_, _, pages), stack = jax.lax.scan(
                body, (toks0, pos0, pages), jnp.arange(n_steps))
            last = jnp.where(limits >= 1, stack[-1], toks0)
            return stack, last, pages

        # slotted scan: no freeze needed for state — slots past their
        # budget only ever complete (and evict/blank) or are free (and are
        # overwritten by the next insert), exactly the slots the
        # synchronous engine also decoded junk into
        def _decode_scan(params, toks0, pool_state, limits):
            def body(carry, k):
                toks, state = carry
                logits, state = jax.vmap(decode_fn, in_axes=(None, 0, 0))(
                    params, toks[:, None, None], state)
                nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
                adv = (k + 1) < limits
                return (jnp.where(adv, nxt, toks), state), nxt
            (_, state), stack = jax.lax.scan(
                body, (toks0, pool_state), jnp.arange(n_steps))
            last = jnp.where(limits >= 1, stack[-1], toks0)
            return stack, last, state

        # sampled twins of the two scans: one extra packed [slots, 4]
        # operand (bitcast temperature/top_p | top_k | seed, see
        # sampling.pack_params) and the absolute token index threaded into
        # the counter-based PRNG.  Rows whose request is greedy lower to
        # argmax inside sample_tokens, so mixed batches stay exact; all-
        # greedy cycles dispatch the plain scans above (byte identity, no
        # sampling operand, no recompile of the greedy path).
        def _decode_scan_paged_sampled(params, toks0, pages, packed, samp):
            table = packed[:, :-2]
            pos0 = packed[:, -2]
            limits = packed[:, -1]

            def body(carry, k):
                toks, pos, pages = carry
                logits, pages = paged_decode_fn(
                    params, toks[:, None],
                    {"pages": pages, "page_table": table, "pos": pos},
                    use_pallas=paged_kernel)
                # the input token sits at pos -> its successor's absolute
                # index is pos + 1; frozen rows idempotently replay the
                # same index, same draw
                nxt = sample_tokens(logits, samp, pos + 1)
                adv = (k + 1) < limits
                return ((jnp.where(adv, nxt, toks),
                         jnp.where(adv, pos + 1, pos), pages), nxt)
            (_, _, pages), stack = jax.lax.scan(
                body, (toks0, pos0, pages), jnp.arange(n_steps))
            last = jnp.where(limits >= 1, stack[-1], toks0)
            return stack, last, pages

        def _decode_scan_sampled(params, toks0, pool_state, limits, samp,
                                 pos0):
            def body(carry, k):
                toks, state, pos = carry
                logits, state = jax.vmap(decode_fn, in_axes=(None, 0, 0))(
                    params, toks[:, None, None], state)
                nxt = sample_tokens(logits[:, 0, :], samp, pos)
                adv = (k + 1) < limits
                return (jnp.where(adv, nxt, toks), state,
                        jnp.where(adv, pos + 1, pos)), nxt
            (_, state, _), stack = jax.lax.scan(
                body, (toks0, pool_state, pos0), jnp.arange(n_steps))
            last = jnp.where(limits >= 1, stack[-1], toks0)
            return stack, last, state

        def _prefill_chunk(params, toks, pages, table, start, n_valid):
            """One request's suffix chunk straight into the page pool
            (pages donated; the scalar/table operands are tiny uploads)."""
            return paged_prefill_fn(params, toks,
                                    {"pages": pages, "page_table": table,
                                     "start": start, "n_valid": n_valid},
                                    use_pallas=paged_kernel)

        verify_tw = self.pool.table_width if self.paged else 0

        def _verify_step(params, toks, pages, packed):
            """One batched speculative verify: every speculating slot's
            [last token, drafts] row forwards through the all-position
            head in a single dispatch (a scan threads the shared pool
            through the rows), then the sampler deterministically replays
            at every drafted index and the accepted prefix is counted
            on-device.

            ``toks`` is [N, spec_width]; ``packed`` is one int32 matrix —
            ``[page table | start | n_valid | sampling params | drafts]``
            per row — so a cycle's whole verify work ships as two host
            uploads however many slots speculate.  Logits row j predicts
            absolute index ``start + 1 + j``; draft j is accepted iff it
            equals exactly the token the non-speculative engine would
            emit there (argmax when greedy, the counter-keyed draw
            otherwise), so acceptance never changes the output stream.
            Padding drafts are -1 and auto-reject, clamping ``nacc`` to
            the real draft count; all-padding rows (``n_valid`` 0) mask
            every position into the trash page."""
            table = packed[:, :verify_tw]
            start = packed[:, verify_tw]
            n_valid = packed[:, verify_tw + 1]
            samp = packed[:, verify_tw + 2:verify_tw + 2 + PACKED_WIDTH]
            drafts = packed[:, verify_tw + 2 + PACKED_WIDTH:]

            def body(pages, row):
                t, tab, st, nv = row
                logits, pages = paged_verify_fn(
                    params, t[None], {"pages": pages, "page_table": tab,
                                      "start": st, "n_valid": nv},
                    use_pallas=paged_kernel)
                return pages, logits

            pages, stack = jax.lax.scan(body, pages,
                                        (toks, table, start, n_valid))
            n = stack.shape[0]
            idx = start[:, None] + 1 + jnp.arange(spec_width,
                                                  dtype=jnp.int32)[None, :]
            rows = jnp.broadcast_to(samp[:, None, :],
                                    (n, spec_width, PACKED_WIDTH))
            emit = sample_tokens(
                stack.reshape(n * spec_width, -1),
                rows.reshape(n * spec_width, PACKED_WIDTH),
                idx.reshape(n * spec_width)).reshape(n, spec_width)
            match = (emit[:, :-1] == drafts) & (drafts >= 0)
            nacc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                           axis=1)
            return emit, nacc, pages

        if self.mesh is not None:
            def ns(spec):
                return jax.sharding.NamedSharding(self.mesh, spec)

            # token / limit vectors are tiny [slots] operands — replicated
            # (the old per-step path dp-sharded toks; at scan granularity
            # the transfer is once per cycle and replication is simpler)
            if self.paged:
                self._decode = jax.jit(
                    _decode_scan_paged,
                    in_shardings=(param_sh, ns(P(None)),
                                  self.pool.shardings,
                                  ns(P(None, None))),
                    out_shardings=(ns(P(None, None)), ns(P(None)),
                                   self.pool.shardings),
                    donate_argnums=(2,))
                self._decode_sampled = jax.jit(
                    _decode_scan_paged_sampled,
                    in_shardings=(param_sh, ns(P(None)),
                                  self.pool.shardings,
                                  ns(P(None, None)), ns(P(None, None))),
                    out_shardings=(ns(P(None, None)), ns(P(None)),
                                   self.pool.shardings),
                    donate_argnums=(2,))
                if self._prefix_path:
                    self._paged_prefill = jax.jit(
                        _counted(_prefill_chunk),
                        in_shardings=(param_sh, ns(P(None, None)),
                                      self.pool.shardings, ns(P(None)),
                                      ns(P()), ns(P())),
                        out_shardings=(ns(P(None, None)),
                                       self.pool.shardings),
                        donate_argnums=(2,))
                if self._spec_path:
                    self._verify = jax.jit(
                        _verify_step,
                        in_shardings=(param_sh, ns(P(None, None)),
                                      self.pool.shardings,
                                      ns(P(None, None))),
                        out_shardings=(ns(P(None, None)), ns(P(None)),
                                       self.pool.shardings),
                        donate_argnums=(2,))
            else:
                self._decode = jax.jit(
                    _decode_scan,
                    in_shardings=(param_sh, ns(P(None)),
                                  self.pool.shardings, ns(P(None))),
                    out_shardings=(ns(P(None, None)), ns(P(None)),
                                   self.pool.shardings),
                    donate_argnums=(2,))
                self._decode_sampled = jax.jit(
                    _decode_scan_sampled,
                    in_shardings=(param_sh, ns(P(None)),
                                  self.pool.shardings, ns(P(None)),
                                  ns(P(None, None)), ns(P(None))),
                    out_shardings=(ns(P(None, None)), ns(P(None)),
                                   self.pool.shardings),
                    donate_argnums=(2,))
        elif self.paged:
            self._decode = jax.jit(_decode_scan_paged, donate_argnums=(2,))
            self._decode_sampled = jax.jit(_decode_scan_paged_sampled,
                                           donate_argnums=(2,))
            if self._prefix_path:
                self._paged_prefill = jax.jit(_counted(_prefill_chunk),
                                              donate_argnums=(2,))
            if self._spec_path:
                self._verify = jax.jit(_verify_step, donate_argnums=(2,))
        else:
            self._decode = jax.jit(_decode_scan, donate_argnums=(2,))
            self._decode_sampled = jax.jit(_decode_scan_sampled,
                                           donate_argnums=(2,))

    # ------------------------------------------------------------------
    # Submission
    # ------------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               priority: int = 0, deadline: Optional[float] = None,
               sampling: Optional[SamplingParams] = None
               ) -> Optional[int]:
        """Queue one request.  Returns its id, or None when the admission
        queue is full (caller sheds load / retries).

        ``sampling`` (default greedy) travels with the request: its seed
        plus the token's absolute index fully determine every draw, so the
        output is a pure function of (prompt, params) — independent of
        batch composition, slot assignment or engine configuration."""
        prompt = tuple(int(t) for t in prompt)
        max_new = (self.cfg.max_new_tokens if max_new_tokens is None
                   else max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + max_new > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"slot capacity max_seq_len={self.cfg.max_seq_len}")
        if sampling is None:
            sampling = GREEDY
        elif not isinstance(sampling, SamplingParams):
            raise TypeError(
                f"sampling must be a SamplingParams, got {type(sampling)}")
        rid = next(self._rid)
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                      priority=priority, deadline=deadline,
                      sampling=sampling)
        if not self.scheduler.submit(req):
            self.metrics.record_reject()
            return None
        self.requests[rid] = req
        self.metrics.record_submit(rid)
        self.tracer.begin("queued", track=request_track(rid),
                          prompt_tokens=len(prompt))
        return rid

    # ------------------------------------------------------------------
    # Event loop
    # ------------------------------------------------------------------

    @property
    def busy(self) -> bool:
        return bool(self.scheduler.depth() or self.pool.owner
                    or self._inflight is not None)

    def _emit(self, req: Request, token: int, stream: Optional[StreamFn]):
        first = not req.tokens
        req.tokens.append(token)
        if first:
            # a resumed preemptee keeps its tokens, so ``first`` is only
            # true on the genuine first emission (even if the request was
            # bounced at admission before ever running)
            self.tracer.instant(
                "request.first_token", track=request_track(req.rid),
                rid=req.rid,
                prefill_s=self.metrics.record_first_token(req.rid))
        else:
            self.metrics.record_token(req.rid)
        done = self._finished(req, token)
        if stream is not None:
            stream(req.rid, token, done)
        return done

    def _finished(self, req: Request, token: int) -> bool:
        if self.cfg.eos_token >= 0 and token == self.cfg.eos_token:
            return True
        return len(req.tokens) >= req.max_new_tokens

    def _complete(self, slot: int, req: Request):
        self.pool.evict(slot)
        self.results[req.rid] = req.tokens
        self.metrics.record_completion(req.rid)
        rt = request_track(req.rid)
        self.tracer.end("decode", track=rt, tokens=len(req.tokens))
        self.tracer.instant("request.complete", track=rt, rid=req.rid,
                            tokens=len(req.tokens),
                            preempted=req.preempted)

    def _finalize(self, slot: int, req: Request):
        """A retired token finished ``req``.  Normally its slot is evicted;
        if it was preempted *after* this cycle was submitted (the slot now
        belongs to someone else or is free), the request is a ghost — its
        in-flight tokens completed it, so it leaves the waiting queue
        without ever being re-admitted."""
        self._drafters.drop(req.rid)
        if self.pool.owner.get(slot) == req.rid:
            self._complete(slot, req)
            return
        self.scheduler.drop(req)
        self.results[req.rid] = req.tokens
        self.metrics.record_completion(req.rid)
        rt = request_track(req.rid)
        self.tracer.end("queued", track=rt)    # re-queued by the preemption
        self.tracer.instant("request.complete", track=rt, rid=req.rid,
                            tokens=len(req.tokens),
                            preempted=req.preempted)

    def _can_admit(self, prompt) -> bool:
        """Would the paged pool take this prompt right now (slot + pages,
        net of prefix-cache hits)?  Used by the priority policy's
        blocked-admission check only — actual admission goes straight
        through the plan phase's ``alloc_*`` (no double planning: the
        pool memoizes the prompt plan by chain hash)."""
        return self.pool.can_admit_prompt(prompt) if self._prefix_path \
            else self.pool.can_admit(len(prompt))

    def _bucketed_prompt(self, prompt, cap: int):
        """(tokens [1, S], n_valid_or_None): pad to a power-of-two bucket
        when the family supports masked tails, else the exact length."""
        n = len(prompt)
        if not self._bucket_slotted:
            return jnp.asarray(np.asarray(prompt, np.int32)[None, :]), None
        toks = np.zeros((1, bucket_len(n, cap)), np.int32)
        toks[0, :n] = prompt
        return jnp.asarray(toks), n

    # ------------------------------------------------------------------
    # Phase 1: plan (host only — decides, allocates, prepares; no blocking)
    # ------------------------------------------------------------------

    def _admitted(self, req: Request, rt: str) -> None:
        """Close the request's queue wait: ``request.admit`` carries it."""
        self.tracer.instant("request.admit", track=rt, rid=req.rid,
                            queue_s=self.metrics.record_admit(req.rid))
        self.tracer.end("queued", track=rt)

    def _plan_admit(self, req: Request, admits: List[_AdmitPlan]) -> bool:
        """Reserve a slot (and pages) for one request; False when the pool
        cannot take it right now (the caller re-queues it, never drops
        it).  The pool is the single admission authority: no pre-check
        re-plans the prompt."""
        prompt = req.resume_prompt()
        rt = request_track(req.rid)
        if self._prefix_path:
            # map cached prefix pages read-only; suffix prefills in chunks
            # (the first chunk is planned this same cycle).  The greedy
            # next-token memo only holds for greedy requests — a sampled
            # request re-prefills its final position and draws its own
            # first token (use_memo=False caps the hit at plen - 1)
            out = self.pool.alloc_prefix(req.rid, prompt,
                                         use_memo=req.sampling.greedy)
            if out is None:
                return False
            slot, cached = out
            if cached:
                self.metrics.record_prefix_hit(cached)
            self._admitted(req, rt)
            self.tracer.begin("prefill", track=rt,
                              prompt_tokens=len(prompt),
                              prefix_hit_tokens=cached)
            if cached >= len(prompt):
                # full hit + memoized next token (cache_next_token): no
                # prefill at all — the admission joins this cycle's decode
                # rows like a completed prefill would
                admits.append(_AdmitPlan(
                    req, slot, prompt,
                    cached_tok=self.pool.cached_next_token(prompt)))
            else:
                self._prefilling[slot] = _PrefillJob(req, prompt, cached)
            return True
        if self.paged:
            slot = self.pool.alloc_for_insert(req.rid, len(prompt))
        else:
            slot = self.pool.alloc(req.rid)
        if slot is None:
            return False
        self._admitted(req, rt)
        self.tracer.begin("prefill", track=rt, prompt_tokens=len(prompt))
        admits.append(_AdmitPlan(req, slot, prompt))
        return True

    def _plan_chunks(self, chunks: List[_ChunkPlan]) -> None:
        """Size one suffix chunk per prefilling slot and prepare its pages
        (ring rotation / COW).  A slot whose chunk finishes the prompt
        leaves ``_prefilling`` now — it joins this same cycle's decode
        rows, exactly when the synchronous engine would have started
        decoding it."""
        for slot in sorted(self._prefilling):
            job = self._prefilling.get(slot)
            if job is None:                 # preempted by an earlier slot's
                continue                    # pressure relief this cycle
            remaining = len(job.prompt) - job.done
            chunk = min(remaining, self.cfg.prefill_chunk_tokens
                        or self._chunk_cap, self._chunk_cap)
            if not self.pool.prepare_chunk(slot, job.done,
                                           job.done + chunk - 1):
                # page pressure mid-prefill (ring rotation needed a COW or
                # fresh page): relieve it like decode growth does — preempt
                # the lowest-priority youngest other request, else bounce
                # this one back to the queue and retry next cycle
                self._relieve_pressure(prefer_not=slot)
                if slot not in self._prefilling or \
                        not self.pool.prepare_chunk(slot, job.done,
                                                    job.done + chunk - 1):
                    continue
            completes = job.done + chunk >= len(job.prompt)
            chunks.append(_ChunkPlan(job, slot, job.done, chunk, completes))
            if completes:
                del self._prefilling[slot]

    def _plan_cycle(self) -> _StepPlan:
        cfg, tr = self.cfg, self.tracer
        # requests with un-retired tokens in flight must not be re-admitted
        # (their resume prompt would miss those tokens); the guard clears
        # at the next retire
        skip_rids = frozenset(self._pending)
        # 1. preemption (priority policy only): fires when admission is
        # blocked — no free slot, or (paged) too few free pages for the
        # most urgent waiter's prompt (prefix-cache hits shrink that need)
        with tr.span("preempt"):
            if cfg.policy == "priority" and self.scheduler.depth():
                head = self.scheduler.peek()
                blocked = (head.rid not in skip_rids
                           and (self.pool.free_slots == 0
                                or (self.paged
                                    and not self._can_admit(
                                        head.resume_prompt()))))
                if blocked:
                    # slots with a speculative verify in flight cannot be
                    # evicted (their retire rewinds pool state in place)
                    running = {s: self.requests[r]
                               for s, r in self.pool.owner.items()
                               if s not in self._spec_wait}
                    for slot, _ in self.scheduler.preemption(running):
                        self._preempt(slot)
        # 2. admission: reserve prefix pages / slots.  When the pool
        # declines (slot free but pages aren't), wait for running work to
        # finish: EVERY not-yet-admitted popped request goes back
        # (reversed, so the head of the line ends up most negative =
        # first) — head-of-line blocking, never a silent drop.
        admits: List[_AdmitPlan] = []
        with tr.span("admit"):
            pending = self.scheduler.next_prefills(self.pool.free_slots,
                                                   skip_rids)
            for i, req in enumerate(pending):
                if not self._plan_admit(req, admits):
                    for r in reversed(pending[i:]):
                        self.scheduler.push_front(r)
                    break
        # 2b. chunked prefill: one chunk per mid-prefill slot per cycle
        chunks: List[_ChunkPlan] = []
        if self._prefilling:
            self._plan_chunks(chunks)
        self.metrics.sample_queue_depth(self.scheduler.depth())
        # 3. per-slot decode budgets: exactly the tokens the request may
        # still emit, net of everything already in flight and the first
        # token this cycle's own prefill will force — the pipeline never
        # over-generates, so EOS-free runs are token-exact by construction
        chunk_done = {c.slot for c in chunks if c.completes}
        override_slots = {a.slot for a in admits} | chunk_done
        limits: Dict[int, int] = {}
        for slot, rid in self.pool.owner.items():
            if slot in self._prefilling:
                continue
            if slot in self._spec_wait:
                # verify in flight: the slot's pos/token chain is only
                # known after retire — it sits this cycle out (depth 2
                # alternates verify / idle cycles per spec slot)
                limits[slot] = 0
                continue
            req = self.requests[rid]
            budget = (req.max_new_tokens - len(req.tokens)
                      - self._pending.get(rid, 0)
                      - (1 if slot in override_slots else 0))
            lim = max(min(budget, cfg.decode_steps, self._span_cap), 0)
            if lim > 0 and slot in chunk_done and self.paged:
                # the chunk's blocks aren't committed to the prefix index
                # until submit — a ring rotation planned now would strand
                # them (see PagedKVCachePool.safe_decode_span)
                lim = self.pool.safe_decode_span(slot, lim)
            limits[slot] = lim
        # 4. page growth for the whole span (paged): every decodable slot
        # gets positions pos..pos+limit-1 privately writable before the
        # scan is dispatched; on starvation preempt until the rest fit
        if self.paged:
            while True:
                starved = self.pool.ensure_decode_capacity(
                    skip=self._prefilling.keys(), steps=limits)
                if not starved:
                    break
                if not self._relieve_pressure():
                    # every evictable tenant has a verify in flight —
                    # starved slots idle one cycle rather than corrupting
                    # an un-retired speculative state
                    for s in starved:
                        limits[s] = 0
                    break
        # held pages peak right after growth (completion evictions come at
        # retire) — sample here so kv_bytes_peak sees the high-water mark
        self.metrics.sample_kv_bytes(self.pool.kv_bytes_held(),
                                     self.pool.kv_bytes_slotted())
        # 5. growth preemption may have evicted work planned above — keep
        # only what the current ownership map still stands behind (nothing
        # was dispatched yet, so a drop here is clean)
        admits = [a for a in admits
                  if self.pool.owner.get(a.slot) == a.req.rid]
        chunks = [c for c in chunks
                  if self.pool.owner.get(c.slot) == c.job.req.rid]
        rows = [(s, self.pool.owner[s]) for s in sorted(self.pool.owner)
                if s not in self._prefilling and limits.get(s, 0) > 0]
        mask = tuple(sorted(s for s in self.pool.owner
                            if limits.get(s, 0) <= 0))
        return _StepPlan(admits, chunks, rows,
                         {s: limits[s] for s, _ in rows}, mask)

    # ------------------------------------------------------------------
    # Phase 1b: draft (host n-gram proposals; swaps decode rows for
    # verify jobs — runs under the ``step.draft`` trace section)
    # ------------------------------------------------------------------

    def _plan_spec(self, plan: _StepPlan) -> None:
        """Pick decode rows to speculate on and draft their continuations.

        A slot is eligible when the host knows its full token history (no
        un-retired emissions: ``_pending`` is zero and no override was
        planned this cycle) and the drafter proposes at least one token.
        The chosen slot's decode row becomes one verify forward over
        [last token, drafts]; its budget covers at most the draft count +
        the correction token, so acceptance can never over-generate.
        Capacity for the m+1-position write span is ensured here; on
        starvation the slot simply keeps its normal decode row — drafting
        never preempts anyone.

        Pipelined engines (depth > 1) plan while the previous cycle is
        still in flight, so a busy slot never has a complete history at
        plan time and could never bootstrap.  When the drafter already
        holds a continuation for the known prefix, the slot idles for one
        cycle (limit 0) so the in-flight tail retires; the next plan then
        drafts from complete history, and verify/idle alternation sustains
        itself from there."""
        if not self._spec_path or not plan.rows:
            return
        cfg = self.cfg
        override_slots = ({a.slot for a in plan.admits}
                          | {c.slot for c in plan.chunks if c.completes})
        for slot, rid in list(plan.rows):
            if slot in override_slots:
                continue
            req = self.requests[rid]
            pending = self._pending.get(rid, 0)
            if pending:
                if (req.tokens and plan.limits.get(slot, 0) > 0
                        and req.max_new_tokens - len(req.tokens)
                        - pending > 1
                        and self._drafters.propose(
                            rid, req.prompt + tuple(req.tokens), 1)):
                    plan.limits[slot] = 0      # stall: drain, draft next
                continue
            if not req.tokens:
                continue
            budget = req.max_new_tokens - len(req.tokens)
            k = min(cfg.spec_tokens, budget - 1)
            if self.pool.layout.window:
                # ring cells alias position p with p - window: each
                # optimistic verify write clobbers the oldest in-window
                # entry, and a rejection cannot restore it (ring rewind
                # keeps cells).  A single draft only ever clobbers the
                # one position that has already left every window a later
                # token can attend to, so windowed slots draft 1.
                k = 1
            if k < 1:
                continue
            drafts = self._drafters.propose(
                rid, req.prompt + tuple(req.tokens), k)
            if not drafts:
                continue
            m = len(drafts)
            # ring windows: the m+1-token write span must stay
            # rotation-free (verify scatters like a chunk, no rotation)
            span = self.pool.safe_decode_span(slot, m + 1)
            if span < 2:
                continue
            if span < m + 1:
                m = span - 1
                drafts = drafts[:m]
            others = [s for s in self.pool.active_slots if s != slot]
            if self.pool.ensure_decode_capacity(skip=others,
                                                steps={slot: m + 1}):
                continue        # page-starved: fall back to plain decode
            plan.specs.append(_SpecPlan(req, slot, drafts,
                                        int(self.pool.pos[slot]), m))
            plan.rows = [r for r in plan.rows if r[0] != slot]
            plan.limits.pop(slot, None)
            plan.mask = tuple(sorted(set(plan.mask) | {slot}))

    # ------------------------------------------------------------------
    # Phase 2: submit (dispatch the plan; advance host positions; no sync)
    # ------------------------------------------------------------------

    def _submit(self, plan: _StepPlan) -> Optional[_InFlight]:
        cfg, tr = self.cfg, self.tracer
        overrides: List[Tuple[int, int, jax.Array]] = []
        for a in plan.admits:
            rt = request_track(a.req.rid)
            if a.cached_tok is not None:
                # full-hit fast path: pages mapped read-only at plan, next
                # token remembered from an earlier identical prefill — the
                # admission costs zero device dispatches beyond seeding the
                # token chain
                self._last_toks_dev = self._set_tok(self._last_toks_dev,
                                                    a.slot, a.cached_tok)
                self._slot_pos[a.slot] = len(a.prompt) + 1
                overrides.append((a.req.rid, a.slot, a.cached_tok))
                tr.end("prefill", track=rt)
                tr.begin("decode", track=rt)
                continue
            toks, n_valid = self._bucketed_prompt(a.prompt, self._cache_len)
            with tr.span("prefill.dispatch", tokens=len(a.prompt),
                         bucket=int(toks.shape[1])):
                if n_valid is None:
                    logits, state = self._prefill(self.params, toks,
                                                  cache_len=self._cache_len)
                else:
                    logits, state = self._prefill(
                        self.params, toks, cache_len=self._cache_len,
                        n_valid=jnp.asarray(n_valid, jnp.int32))
            self.metrics.record_prefill(len(a.prompt))
            if self.paged:
                self.pool.insert_state(a.slot, state)
            else:
                self.pool.insert_at(a.slot, state)
            if a.req.sampling.greedy:
                tok = self._argmax1(logits)
            else:
                tok = self._sample1(
                    logits, jnp.asarray(pack_params(a.req.sampling)),
                    jnp.asarray(len(a.prompt), jnp.int32))
            self._last_toks_dev = self._set_tok(self._last_toks_dev,
                                                a.slot, tok)
            self._slot_pos[a.slot] = len(a.prompt) + 1
            overrides.append((a.req.rid, a.slot, tok))
            tr.end("prefill", track=rt)
            tr.begin("decode", track=rt)
        for c in plan.chunks:
            job = c.job
            width = (bucket_len(c.chunk, self.pool.padded_len)
                     if cfg.prefill_bucket else c.chunk)
            ctoks = np.zeros((1, width), np.int32)
            ctoks[0, :c.chunk] = job.prompt[c.start:c.start + c.chunk]
            rt = request_track(job.req.rid)
            with tr.span("prefill.chunk", track=rt, chunk=c.chunk,
                         bucket=width, start=c.start):
                with tr.span("prefill.dispatch", tokens=c.chunk,
                             bucket=width):
                    # the table row uploads as a copy (jnp.array, see
                    # PagedKVCachePool.insert_state)
                    logits, self.pool.pages = self._paged_prefill(
                        self.params, jnp.asarray(ctoks), self.pool.pages,
                        jnp.array(self.pool.tables[c.slot]),
                        jnp.asarray(c.start, jnp.int32),
                        jnp.asarray(c.chunk, jnp.int32))
            self.metrics.record_prefill(c.chunk)
            job.done = c.start + c.chunk
            # register fully-written blocks right away: requests admitted
            # next cycle can already share this prefix (device order makes
            # the pages valid before any reader dispatches)
            self.pool.commit_prefix(c.slot, job.prompt[:job.done])
            if c.completes:
                if job.req.sampling.greedy:
                    tok = self._argmax1(logits)
                    # remember (prompt -> next token) so a repeat of this
                    # exact prompt can skip prefill entirely (full-hit
                    # fast path); the memo is greedy-only — a sampled
                    # request's first token depends on its seed
                    self.pool.cache_next_token(job.prompt, tok)
                else:
                    tok = self._sample1(
                        logits, jnp.asarray(pack_params(job.req.sampling)),
                        jnp.asarray(len(job.prompt), jnp.int32))
                self._last_toks_dev = self._set_tok(self._last_toks_dev,
                                                    c.slot, tok)
                self._slot_pos[c.slot] = len(job.prompt) + 1
                overrides.append((job.req.rid, c.slot, tok))
                tr.end("prefill", track=rt)
                tr.begin("decode", track=rt)
        stack = None
        if plan.rows:
            # all-greedy cycles take the plain argmax scan (byte-identical
            # dispatch to the pre-sampling engine); any sampled row routes
            # the whole cycle through the sampled twin, whose greedy rows
            # still lower to argmax inside sample_tokens
            sampled = any(not self.requests[rid].sampling.greedy
                          for _, rid in plan.rows)
            samp_dev = None
            if sampled:
                samp = np.stack(
                    [pack_params(self.requests[self.pool.owner[s]].sampling
                                 if s in self.pool.owner else GREEDY)
                     for s in range(cfg.max_batch)])
                samp_dev = jnp.asarray(samp)
                self._sampled_dispatches += 1
                self._topk_dispatches += int((samp[:, 2] > 0).any())
            tr.counter("sampler.sampled_dispatches", self._sampled_dispatches)
            tr.counter("sampler.topk_dispatches", self._topk_dispatches)
            with tr.span("decode.dispatch", steps=cfg.decode_steps,
                         rows=len(plan.rows), sampled=sampled):
                if self.paged:
                    packed = self.pool.decode_operands(
                        plan.limits, mask_slots=plan.mask)
                    if sampled:
                        stack, self._last_toks_dev, self.pool.pages = \
                            self._decode_sampled(
                                self.params, self._last_toks_dev,
                                self.pool.pages, packed, samp_dev)
                    else:
                        stack, self._last_toks_dev, self.pool.pages = \
                            self._decode(self.params, self._last_toks_dev,
                                         self.pool.pages, packed)
                else:
                    limits_dev = jnp.asarray(np.asarray(
                        [plan.limits.get(s, 0) for s in range(cfg.max_batch)],
                        np.int32))
                    if sampled:
                        pos_dev = jnp.asarray(
                            self._slot_pos.astype(np.int32))
                        stack, self._last_toks_dev, self.pool.state = \
                            self._decode_sampled(
                                self.params, self._last_toks_dev,
                                self.pool.state, limits_dev, samp_dev,
                                pos_dev)
                    else:
                        stack, self._last_toks_dev, self.pool.state = \
                            self._decode(self.params, self._last_toks_dev,
                                         self.pool.state, limits_dev)
            if self.paged:
                # host positions are deterministic once planned — advance
                # now so the next plan overlaps the in-flight device step
                self.pool.advance(steps=plan.limits)
            for slot, _ in plan.rows:
                self._slot_pos[slot] += plan.limits[slot]
        # speculative verifies: one batched fixed-width forward covering
        # every drafted slot this cycle ([last token, drafts, pad] per
        # row), after the decode scan so the donated page buffer threads
        # through in dispatch order.  The row count pads to the next
        # power of two (a handful of compiles per engine) with inert
        # rows: table 0 routes writes to the trash page, n_valid 0 masks
        # them, drafts -1 auto-reject.  Two host uploads total — the
        # token rows and one packed int32 matrix carrying
        # [page table | start | n_valid | sampling | drafts] per row.
        specs: Optional[Tuple[List[_SpecPlan], jax.Array, jax.Array]] = None
        if plan.specs:
            width = cfg.spec_tokens + 1
            tw = self.pool.table_width
            n = len(plan.specs)
            n_pad = 1 << (n - 1).bit_length()
            toks = np.zeros((n_pad, width), np.int32)
            packed = np.zeros((n_pad, tw + 2 + PACKED_WIDTH + width - 1),
                              np.int32)
            packed[:, tw + 2 + PACKED_WIDTH:] = -1
            total = 0
            for i, sp in enumerate(plan.specs):
                toks[i, 0] = sp.req.tokens[-1]
                toks[i, 1:1 + sp.m] = sp.drafts
                packed[i, :tw] = self.pool.tables[sp.slot]
                packed[i, tw] = sp.start
                packed[i, tw + 1] = sp.m + 1
                packed[i, tw + 2:tw + 2 + PACKED_WIDTH] = \
                    pack_params(sp.req.sampling)
                packed[i, tw + 2 + PACKED_WIDTH:
                       tw + 2 + PACKED_WIDTH + sp.m] = sp.drafts
                total += sp.m + 1
            with tr.span("verify.dispatch", tokens=total, rows=n):
                emit, nacc, self.pool.pages = self._verify(
                    self.params, jnp.asarray(toks), self.pool.pages,
                    jnp.asarray(packed))
            for sp in plan.specs:
                # optimistic host advance over the whole drafted span;
                # retire rewinds past the first mismatch
                self.pool.advance(steps={sp.slot: sp.m + 1})
                self._slot_pos[sp.slot] += sp.m + 1
                self._spec_wait.add(sp.slot)
                self._pending[sp.req.rid] = (self._pending.get(sp.req.rid, 0)
                                             + sp.m + 1)
            specs = (list(plan.specs), emit, nacc)
        for rid, _, _ in overrides:
            self._pending[rid] = self._pending.get(rid, 0) + 1
        for slot, rid in plan.rows:
            self._pending[rid] = self._pending.get(rid, 0) + plan.limits[slot]
        if not overrides and stack is None and specs is None:
            return None
        return _InFlight(overrides, plan.rows, plan.limits, stack,
                         cfg.decode_steps, specs)

    # ------------------------------------------------------------------
    # Phase 3: retire (materialise the previous cycle; emit in sync order)
    # ------------------------------------------------------------------

    def _dec_pending(self, rid: int, n: int) -> None:
        if n <= 0:
            return
        left = self._pending.get(rid, 0) - n
        if left > 0:
            self._pending[rid] = left
        else:
            self._pending.pop(rid, None)

    def _retire(self, inf: _InFlight, stream: Optional[StreamFn]) -> None:
        # every host <- device sync of the engine sits in a ``retire.wait``
        # span: the time the host waited for the device
        tr = self.tracer
        stack = None
        if inf.stack is not None:
            with tr.span("retire.wait"):
                stack = np.asarray(inf.stack)
        emitted: List[int] = []
        for rid, slot, tok in inf.overrides:
            if rid in self.results:
                continue
            req = self.requests[rid]
            emitted.append(rid)
            with tr.span("retire.wait"):
                tok = int(tok)
            if self._emit(req, tok, stream):
                self._finalize(slot, req)
        # decode rows emit step-major / slot-minor — the synchronous
        # engine's per-step completion sweep order.  Rows past a slot's
        # budget or past an EOS cut (``rid in results``) are speculative
        # device output and are dropped here.
        for k in range(inf.n_steps):
            for slot, rid in inf.rows:
                if k >= inf.limits.get(slot, 0) or rid in self.results:
                    continue
                req = self.requests[rid]
                emitted.append(rid)
                self.metrics.record_decode_token()
                if self._emit(req, int(stack[k, slot]), stream):
                    self._finalize(slot, req)
        # speculative verifies: sync the whole cycle's accept counts and
        # emitted rows in one host transfer each, rewind each slot past
        # its first mismatch (freeing over-allocated tail pages) and emit
        # accepted drafts + the correction token — exactly the tokens
        # sequential decode would have produced, just computed in one
        # batched forward instead of sum(nacc + 1) steps
        if inf.specs is not None:
            sps, emit_dev, nacc_dev = inf.specs
            with tr.span("retire.wait"):
                emit_all = np.asarray(emit_dev)
                nacc_all = np.asarray(nacc_dev)
            for i, sp in enumerate(sps):
                req, slot = sp.req, sp.slot
                self._spec_wait.discard(slot)
                self._dec_pending(req.rid, sp.m + 1)
                nacc = int(nacc_all[i])
                self.metrics.record_spec(sp.m, nacc)
                if (self.pool.owner.get(slot) != req.rid
                        or req.rid in self.results):
                    continue    # defensive: spec slots are never preempted
                emit = emit_all[i]
                new_pos = sp.start + 1 + nacc   # last accepted index
                self.pool.rewind(slot, new_pos)
                self._slot_pos[slot] = new_pos + 1
                self._last_toks_dev = self._set_tok(
                    self._last_toks_dev, slot, int(emit[nacc]))
                for j in range(nacc + 1):
                    emitted.append(req.rid)
                    self.metrics.record_decode_token()
                    if self._emit(req, int(emit[j]), stream):
                        self._finalize(slot, req)
                        break
        # ghost hygiene: a victim preempted after this cycle was submitted
        # had its ITL baseline dropped by the preemption — the emissions
        # above re-seeded it, so drop it again to keep the requeue ->
        # resume gap out of inter-token latency
        owned = set(self.pool.owner.values())
        for rid in emitted:
            if rid not in self.results and rid not in owned:
                self.metrics.drop_itl_baseline(rid)
        # symmetric pending release (EOS cuts don't change what was
        # dispatched, so the decrement mirrors the submit-side increment)
        for rid, _, _ in inf.overrides:
            self._dec_pending(rid, 1)
        for slot, rid in inf.rows:
            self._dec_pending(rid, inf.limits.get(slot, 0))

    # ------------------------------------------------------------------
    # Preemption helpers (shared by plan-phase policies)
    # ------------------------------------------------------------------

    def _preempt(self, slot: int):
        """Evict a running request and put it back at the queue head; its
        emitted tokens fold into the resume prompt (greedy decode, so the
        eventual output is unchanged).  A victim caught mid-prefill simply
        restarts its suffix on resume (its shared prefix pages stay cached,
        so the lost work is the uncommitted chunks only).  A victim with
        un-retired tokens in flight stays un-admittable until they emit
        (``_pending`` / ``skip_rids``)."""
        victim = self.requests[self.pool.owner[slot]]
        self._prefilling.pop(slot, None)
        self.pool.evict(slot)
        self.scheduler.requeue(victim)
        self.metrics.record_preemption(victim.rid)
        # close whichever lifecycle span the victim had open (end() of a
        # not-open span is a silent no-op) and put it back to "queued"
        rt = request_track(victim.rid)
        self.tracer.end("prefill", track=rt, preempted=True)
        self.tracer.end("decode", track=rt, preempted=True)
        self.tracer.instant("request.preempt", track=rt, rid=victim.rid,
                            preemptions=victim.preempted)
        self.tracer.begin("queued", track=rt, resumed=True)

    def _relieve_pressure(self, prefer_not: Optional[int] = None) -> bool:
        """Preempt the lowest-priority, youngest running request to free
        pages — preferring a victim other than ``prefer_not`` (a slot
        mid-prefill that triggered the pressure preempts itself only when
        it is the lone tenant).  Recency is judged by rid (monotone
        submission order): ``arrival_seq`` goes negative on requeue, so it
        cannot rank original arrivals.  Slots with a speculative verify in
        flight are never victims (their retire rewinds pool state in
        place); returns False when that leaves no candidate."""
        candidates = [s for s in self.pool.active_slots
                      if s != prefer_not and s not in self._spec_wait]
        if not candidates:
            candidates = [s for s in self.pool.active_slots
                          if s not in self._spec_wait]
        if not candidates:
            return False
        self._preempt(max(
            candidates,
            key=lambda s: (-self.requests[self.pool.owner[s]].priority,
                           self.pool.owner[s])))
        return True

    # ------------------------------------------------------------------
    # The cycle
    # ------------------------------------------------------------------

    def step(self, stream: Optional[StreamFn] = None) -> bool:
        """One engine cycle; returns True while work remains.

        ``pipeline_depth=2``: plan and submit cycle N+1, then retire cycle
        N — the host plans against the in-flight device step and the
        device is never idle waiting for planning.  ``pipeline_depth=1``:
        retire what was just submitted (synchronous semantics).

        Traced (``ServeConfig(trace=True)``, or during a profiler session
        whatever ``trace`` says), the cycle decomposes into the section
        spans of ``repro.obs.export.STEP_SECTIONS`` (``step.plan`` /
        ``step.submit`` / ``step.retire`` tile the enclosing ``step``
        span); ``*.dispatch`` spans time enqueueing device work and
        ``retire.wait`` the host blocking on its results — nothing is
        fenced, so a traced cycle dispatches exactly like an untraced one.
        Untraced and unprofiled, every ``with tracer.span(...)`` is the
        shared no-op context manager.
        """
        tr = self.tracer
        with tr.span("step"):
            with tr.span("step.plan"):
                plan = self._plan_cycle()
            with tr.span("step.draft", rows=len(plan.rows)):
                self._plan_spec(plan)
            # prompt tokens dispatched this cycle, beside the decode rows:
            # the mixed cycles whose prefill delays the decode scan
            prefill_tokens = (
                sum(len(a.prompt) for a in plan.admits
                    if a.cached_tok is None)
                + sum(c.chunk for c in plan.chunks))
            with tr.span("step.submit", prefill_tokens=prefill_tokens,
                         decode_rows=len(plan.rows)):
                nxt = self._submit(plan)
                prev, self._inflight = self._inflight, nxt
                if prev is not None or nxt is not None:
                    tr.counter(INFLIGHT_COUNTER,
                               int(prev is not None) + int(nxt is not None))
            if self.cfg.pipeline_depth == 1:
                # prev is always None at depth 1 — retire this very cycle
                prev, self._inflight = self._inflight, None
            with tr.span("step.retire", pending=prev is not None):
                if prev is not None:
                    self._retire(prev, stream)
                    tr.counter(INFLIGHT_COUNTER,
                               int(self._inflight is not None))
        return self.busy

    def run(self, stream: Optional[StreamFn] = None) -> Dict[int, List[int]]:
        """Drive the loop until queue, slots and pipeline drain; returns
        rid -> tokens."""
        while self.step(stream):
            pass
        return dict(self.results)

    # ------------------------------------------------------------------
    # Observability
    # ------------------------------------------------------------------

    def save_trace(self, path: str) -> Optional[str]:
        """Write the tracer's ring buffer as a Perfetto-loadable Chrome
        trace JSON (``{"traceEvents": [...]}``); None when the engine runs
        untraced (``ServeConfig(trace=False)`` — nothing was recorded)."""
        if not self.tracer.enabled:
            return None
        return write_chrome_trace(self.tracer, path)

    # ------------------------------------------------------------------
    # Convenience: serve a closed batch of prompts
    # ------------------------------------------------------------------

    def generate(self, prompts, max_new_tokens: Optional[int] = None,
                 stream: Optional[StreamFn] = None,
                 sampling=None) -> List[List[int]]:
        """Submit ``prompts`` (list of token lists) and run to completion.

        ``sampling`` is one ``SamplingParams`` applied to every prompt, or
        a per-prompt list (None entries mean greedy).

        A closed batch larger than ``max_queue`` is fed with backpressure:
        when the admission queue is full the engine cycles until it drains
        (running requests finish and free slots), then keeps submitting —
        no request of a closed batch is ever shed.
        """
        if sampling is None or isinstance(sampling, SamplingParams):
            per_req = [sampling] * len(prompts)
        else:
            per_req = list(sampling)
            if len(per_req) != len(prompts):
                raise ValueError(
                    f"sampling list length {len(per_req)} != "
                    f"{len(prompts)} prompts")
        rids = []
        for p, sp in zip(prompts, per_req):
            while self.scheduler.depth() >= self.cfg.max_queue:
                self.step(stream)
            rid = self.submit(p, max_new_tokens, sampling=sp)
            if rid is None:
                raise RuntimeError("queue admitted past max_queue")
            rids.append(rid)
        out = self.run(stream)
        return [out[r] for r in rids]
