"""The user-transparent Session: one object, three workloads.

The paper's thesis (MaTEx-TensorFlow §III) is that the *user script* stays
sequential and the *runtime* owns distribution.  ``repro.api`` is where that
thesis meets the repo's surface area: ``load(arch)`` returns a ``Session``
that owns the mesh/sharding lifecycle, the resolved configs and the registry
bundle, and exposes

  * ``session.train(steps=...)``      — TransparentTrainer + sharded data +
                                        checkpoint / elastic restore,
  * ``session.serve(requests)``       — the continuous-batching engine
                                        (paged or slotted KV, chosen by the
                                        bundle's declared capabilities),
  * ``session.generate(prompt(s))``   — one-shot greedy generation over the
                                        same engine.

Distribution is config, not code: ``load(arch, mesh="4x2")`` runs the same
script data-parallel x tensor-parallel; ``load(arch)`` runs it on one
device.  Capability errors surface at load time (``require=("serve",)``) or
as a one-line ``CapabilityError`` on first use — never as an ``is None``
crash mid-run.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import (Any, Dict, Iterable, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np

from repro.configs import get_config
from repro.configs.base import (MeshConfig, ModelConfig, OptimizerConfig,
                                RunConfig, ServeConfig, ShapeConfig)
from repro.models import registry

MeshLike = Union[None, str, Tuple[int, ...], MeshConfig]

#: axis names by mesh rank: "4" -> 4x1 data x model, "4x2" -> data x model,
#: "2x4x2" -> pod x data x model (the paper's two-pod layout)
_AXES_BY_RANK = {2: ("data", "model"), 3: ("pod", "data", "model")}

#: auto-sized serve capacity rounds up to this bucket (bounds engine-cache
#: cardinality under varying prompt lengths)
_SEQ_BUCKET = 64

#: engines kept per Session (oldest evicted; each holds a full KV pool)
_MAX_ENGINES = 4


class CapabilityError(ValueError):
    """A workload the loaded family does not declare (see
    ``ModelBundle.capabilities``)."""


def parse_mesh(spec: MeshLike) -> Optional[MeshConfig]:
    """``"2x2"`` / ``(2, 2)`` / ``MeshConfig`` / ``None`` -> ``MeshConfig``.

    Strings are ``D``, ``DxM`` or ``PxDxM`` (data / model / pod extents);
    this is the single parser behind ``--mesh`` in every launch driver and
    the ``mesh=`` argument of ``repro.api.load``.
    """
    if spec is None or spec == "":
        return None
    if isinstance(spec, MeshConfig):
        spec.validate()
        return spec
    if isinstance(spec, str):
        try:
            shape = tuple(int(x) for x in spec.lower().split("x"))
        except ValueError:
            raise ValueError(
                f"mesh spec {spec!r} is not of the form 'D', 'DxM' or "
                "'PxDxM' (e.g. '2x2' = 2-way data x 2-way model)") from None
    else:
        shape = tuple(int(x) for x in spec)
    if len(shape) == 1:
        # pure DP (the paper's setting): normalize to a 2-D mesh with a
        # size-1 model axis — the sharding rules always name "model"
        shape = shape + (1,)
    axes = _AXES_BY_RANK.get(len(shape))
    if axes is None or any(s < 1 for s in shape):
        raise ValueError(
            f"mesh shape {shape} must be 1-3 positive extents "
            "(data | data x model | pod x data x model)")
    return MeshConfig(shape=shape, axis_names=axes)


@dataclass
class TrainResult:
    """What ``Session.train`` hands back: the per-step loss trajectory plus
    the last step's metrics and the straggler-monitor summary."""
    losses: List[float]
    metrics: Dict[str, float]
    step: int
    elapsed_s: float
    straggler: Dict[str, float] = field(default_factory=dict)

    @property
    def loss(self) -> float:
        return self.losses[-1] if self.losses else float("nan")


class Session:
    """One architecture bound to one (optional) mesh; owns params, trainer
    and engine lifecycles so user scripts never touch them directly.

    Construct through :func:`load`.  The Session is lazy: nothing touches
    jax device state until the first ``train`` / ``serve`` / ``generate``
    call, so sessions can be created before a driver decides device counts.
    """

    def __init__(self, model_cfg: ModelConfig,
                 mesh_cfg: Optional[MeshConfig] = None, *, seed: int = 0,
                 dp_mode: Optional[str] = None,
                 allreduce: Optional[str] = None, params=None):
        model_cfg.validate()
        self.model = model_cfg
        self.mesh_cfg = mesh_cfg
        self.seed = seed
        self._mesh_overrides = {k: v for k, v in
                                (("dp_mode", dp_mode), ("allreduce", allreduce))
                                if v is not None}
        self.bundle = registry.build(model_cfg)
        self._mesh = None
        self._params = None
        self._init_params = params     # given at load: train starts here
        self._trainer = None
        self._trainer_key = None
        self._train_state = None
        self._stream = None            # (iterator, prefetcher) kept across
        self._stream_key = None        # train() calls: data must not replay
        self._engines: Dict[ServeConfig, Any] = {}
        self._last_engine = None

    # -- capabilities ------------------------------------------------------

    def capabilities(self) -> frozenset:
        """Declared decode-path contracts of the loaded family
        (subset of ``registry.CAPABILITIES``)."""
        return self.bundle.capabilities()

    def _require(self, cap: str):
        if cap not in self.capabilities():
            raise CapabilityError(
                f"{self.model.name} ({self.model.family}) doesn't {cap} yet: "
                f"declared capabilities are {sorted(self.capabilities())} — "
                "see ModelBundle.capabilities / ROADMAP.md")

    # -- mesh / params lifecycle ------------------------------------------

    def _train_mesh_cfg(self) -> MeshConfig:
        base = self.mesh_cfg or MeshConfig(shape=(1, 1),
                                           axis_names=("data", "model"))
        return dataclasses.replace(base, **self._mesh_overrides) \
            if self._mesh_overrides else base

    @property
    def mesh(self):
        """The jax device mesh (built on first use; None when meshless)."""
        if self.mesh_cfg is None:
            return None
        if self._mesh is None:
            import jax
            from repro.launch.mesh import build_mesh
            need = self.mesh_cfg.num_devices
            devices = jax.devices()
            have = len(devices)
            if need > have:
                if devices[0].platform == "cpu":
                    hint = ("set XLA_FLAGS=--xla_force_host_platform_device_"
                            "count=N (before the first jax import) or pass "
                            "--devices to the launchers")
                else:
                    hint = (f"this host has {have} {devices[0].device_kind} "
                            f"device(s); use a mesh of at most {have}")
                raise ValueError(
                    f"mesh {self.mesh_cfg.shape} needs {need} devices but "
                    f"only {have} are visible; {hint}")
            self._mesh = build_mesh(self._train_mesh_cfg())
        return self._mesh

    @property
    def params(self):
        """Current parameters: the trained state's params once ``train`` has
        run, otherwise those given to ``load`` or a seeded init (shared by
        serve/generate)."""
        if self._train_state is not None:
            return self._train_state.params
        if self._params is None:
            import jax
            init = self.bundle.init_params
            if self.mesh_cfg is not None:
                # initialize straight into the mesh layout: a full copy on
                # one device would not fit beside its shards at real size
                from repro.models import common
                shardings = common.logical_to_mesh(
                    self.bundle.specs, self.mesh,
                    common.rules_for(self.mesh_cfg, self.model))
                init = jax.jit(init, out_shardings=shardings)
            if self._init_params is not None:
                self._params = jax.device_put(
                    self._init_params,
                    shardings if self.mesh_cfg is not None else None)
            else:
                self._params = init(jax.random.PRNGKey(self.seed))
        return self._params

    # -- train -------------------------------------------------------------

    def train(self, steps: int = 50, *, data=None, seq_len: int = 64,
              global_batch: int = 8, optimizer: Optional[OptimizerConfig] = None,
              lr: Optional[float] = None, microbatch: int = 0,
              ckpt_dir: str = "", ckpt_every: int = 25, resume: bool = False,
              log_every: int = 0) -> TrainResult:
        """Run ``steps`` training steps of the sequential ``loss_fn``; the
        runtime injects broadcast init, gradient all-reduce and rank-sharded
        data (the paper's §III-D/F), plus checkpointing when ``ckpt_dir`` is
        set and elastic restore when ``resume`` is.

        ``data`` is a ``repro.data.readers.DataSet`` (default: synthetic
        tokens seeded from the session seed).  Repeated calls with the same
        shape/optimizer knobs continue from the current state.
        """
        self._require("train")
        import jax
        from repro.checkpoint.checkpoint import latest_step, save_checkpoint
        from repro.checkpoint.elastic import restore_elastic
        from repro.checkpoint.failures import StragglerMonitor
        from repro.core.transparent import TransparentTrainer
        from repro.data.pipeline import make_input_pipeline
        from repro.data.readers import synthetic_tokens
        from repro.obs import TRAIN_TRACER as tr
        from repro.obs import TRAIN_TRACK

        opt = optimizer or OptimizerConfig(
            name="adam", lr=1e-3 if lr is None else lr)
        if optimizer is not None and lr is not None:
            opt = dataclasses.replace(opt, lr=lr)
        mesh_cfg = self._train_mesh_cfg()
        key = (seq_len, global_batch, opt, microbatch, mesh_cfg)
        if self._trainer is not None and self._trainer_key != key \
                and self._train_state is not None:
            import warnings
            warnings.warn(
                "train() knobs changed (shape/optimizer/mesh): the trained "
                "state is discarded and training restarts from a fresh "
                "init; pass the same knobs to continue a run",
                stacklevel=2)
        if self._trainer is None or self._trainer_key != key:
            run = RunConfig(
                model=self.model,
                shape=ShapeConfig("api", "train", seq_len, global_batch),
                mesh=mesh_cfg, optimizer=opt, seed=self.seed,
                microbatch=microbatch)
            self._trainer = TransparentTrainer.from_bundle(
                run, self.bundle, mesh=self.mesh)
            self._trainer_key = key
            self._train_state = None
        trainer = self._trainer

        # the data stream persists across train() calls: a continuation
        # consumes the *next* batches, never a replay of already-seen ones
        # (train(N) + train(N) == train(2N) for identical knobs)
        stream_key = (key, None if data is None else id(data))
        if self._stream is None or self._stream_key != stream_key:
            if self._stream is not None:
                self._stream[1].close()
            if data is None:
                data = synthetic_tokens(
                    self.model.vocab_size, seq_len,
                    num_samples=global_batch * 64, seed=self.seed,
                    rank=jax.process_index(),
                    world=max(jax.process_count(), 1))
            self._stream = make_input_pipeline(
                data, global_batch, trainer.mesh, trainer.dp_axes,
                seed=self.seed)
            self._stream_key = stream_key
        it, pf = self._stream

        if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
            self._train_state, start = restore_elastic(ckpt_dir, trainer)
        elif self._train_state is None:
            self._train_state = trainer.init(self.seed,
                                             params=self._init_params)
            start = 0
        else:
            start = int(jax.device_get(self._train_state.step))
        state = self._train_state
        monitor = StragglerMonitor()

        losses: List[float] = []
        t_start = time.time()
        step = start
        tokens_per_step = global_batch * seq_len
        try:
            while True:
                with tr.span("train.data", track=TRAIN_TRACK):
                    batch = next(it, None)
                if batch is None:
                    break
                t0 = time.time()
                with tr.span("train.step", track=TRAIN_TRACK):
                    state, m = trainer.step(state, batch)
                monitor.record(time.time() - t0)
                with tr.span("train.sync", track=TRAIN_TRACK):
                    step = int(m["step"])
                    losses.append(float(m["loss"]))
                tr.counter("train.steps", step, track=TRAIN_TRACK)
                tr.counter("train.tokens", step * tokens_per_step,
                           track=TRAIN_TRACK)
                if log_every and (step % log_every == 0 or step == start + 1):
                    print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                          f"gnorm {float(m['grad_norm']):.3f}", flush=True)
                if ckpt_dir and ckpt_every and step % ckpt_every == 0:
                    save_checkpoint(ckpt_dir, state, step, blocking=False)
                if step >= start + steps:
                    break
        except BaseException:
            # stream state is unknown mid-batch: drop it so the next call
            # starts a fresh pipeline (the prefetch thread is daemon)
            self._stream[1].close()
            self._stream = None
            raise
        if ckpt_dir:
            save_checkpoint(ckpt_dir, state, step, blocking=True)
        self._train_state = state
        self._engines.clear()          # serving must see the new params
        self._last_engine = None
        metrics = {"loss": losses[-1] if losses else float("nan"),
                   "grad_norm": float(m["grad_norm"]) if losses else 0.0,
                   "step": step}
        return TrainResult(losses=losses, metrics=metrics, step=step,
                           elapsed_s=time.time() - t_start,
                           straggler=monitor.summary())

    # -- serve / generate --------------------------------------------------

    def _serve_cfg(self, prompts: Sequence[Sequence[int]],
                   max_new: Optional[int],
                   overrides: Dict[str, Any]) -> ServeConfig:
        """Resolve a ServeConfig: explicit knobs win, the rest auto-sizes to
        the submitted batch (longest prompt + generation budget)."""
        auto: Dict[str, Any] = {}
        if prompts:
            longest = max(len(p) for p in prompts)
            budget = max_new if max_new is not None else \
                overrides.get("max_new_tokens", 32)
            # bucket both knobs so varying batch sizes / prompt lengths
            # reuse one compiled engine instead of keying a new ServeConfig
            # (and a new fixed-shape XLA decode + KV pool) per distinct call
            auto["max_batch"] = min(
                8, 1 << max(len(prompts) - 1, 0).bit_length())
            need = longest + budget
            auto["max_seq_len"] = -(-need // _SEQ_BUCKET) * _SEQ_BUCKET
        if max_new is not None:
            auto["max_new_tokens"] = max_new
        auto.update(overrides)
        seq = auto.get("max_seq_len", ServeConfig.max_seq_len)
        if "page_size" not in auto:
            # auto-size pages to the model's layout: shrink for short
            # batches and to tile windowed-attention rings
            # (KVLayout.max_page_size); floor_pow2 keeps the default
            # enable_prefix_cache block hashing valid
            from repro.configs.base import floor_pow2
            cap = floor_pow2(seq)
            layout = self.bundle.kv_layout
            if layout is not None:
                cap = min(cap, layout.max_page_size())
            if ServeConfig.page_size > cap:
                auto["page_size"] = cap
        return ServeConfig(**auto)

    def _drop_engine(self, key) -> None:
        """Retire one cached engine, invalidating its prefix cache first —
        a retired pool's cached pages must never survive into a later
        engine's view of 'cached' state."""
        eng = self._engines.pop(key)
        if getattr(eng, "paged", False):
            eng.pool.clear_prefix_cache()
        if self._last_engine is eng:
            self._last_engine = None

    def _engine_for(self, serve_cfg: ServeConfig):
        from repro.serving import ServingEngine
        # switching kv_layout / kv_dtype (or mutating the model's
        # attn_kind) on a live Session retires every engine built for a
        # different layout: a stale ServeConfig-keyed engine would
        # otherwise survive with an incompatible pool (and a prefix cache
        # the caller believes gone)
        for key in [k for k, e in self._engines.items()
                    if k.kv_layout != serve_cfg.kv_layout
                    or k.kv_dtype != serve_cfg.kv_dtype
                    or e.model_cfg.attn_kind != self.model.attn_kind]:
            self._drop_engine(key)
        eng = self._engines.pop(serve_cfg, None)
        if eng is None:
            eng = ServingEngine(self.model, serve_cfg, params=self.params,
                                mesh_cfg=self.mesh_cfg, seed=self.seed)
        self._engines[serve_cfg] = eng          # re-insert = LRU touch
        while len(self._engines) > _MAX_ENGINES:
            self._drop_engine(next(iter(self._engines)))
        self._last_engine = eng
        return eng

    @property
    def train_state(self):
        """The trained ``TrainState`` (params, optimizer state, step) of
        the last ``train`` call (None before the first)."""
        return self._train_state

    @property
    def trainer(self):
        """The transparent trainer of the last ``train`` call (None
        before): its ``lower_step`` shows the compiled step."""
        return self._trainer

    @property
    def engine(self):
        """The most recently used serving engine (metrics live here)."""
        return self._last_engine

    @property
    def tracer(self):
        """The last-used engine's ``repro.obs`` tracer (``NULL_TRACER``
        when that engine ran untraced; None before any serve call).  Turn
        tracing on per call: ``session.serve(..., trace=True)``."""
        return None if self._last_engine is None else self._last_engine.tracer

    def save_trace(self, path: str) -> Optional[str]:
        """Write the last serve's Chrome trace JSON (Perfetto-loadable —
        ui.perfetto.dev / chrome://tracing); None when no engine has run
        or the last serve was untraced."""
        if self._last_engine is None:
            return None
        return self._last_engine.save_trace(path)

    def serve(self, requests: Sequence[Sequence[int]], *,
              max_new: Optional[int] = None, stream=None,
              serve_cfg: Optional[ServeConfig] = None,
              sampling=None, **serve_overrides) -> List[List[int]]:
        """Continuous-batching generation for a closed batch of prompts
        (lists of token ids); returns one token list per prompt, in order.

        Pass a full ``serve_cfg`` for total control, or individual
        ``ServeConfig`` field overrides as keyword arguments
        (``policy="priority"``, ``kv_layout="paged"``,
        ``enable_prefix_cache=False``, ``prefill_chunk_tokens=256``, ...).
        ``sampling`` is one ``repro.serving.SamplingParams`` for every
        prompt or a per-prompt list (None = greedy).  Decode — greedy or
        sampled — is token-identical to serving each prompt alone:
        sampling keys its PRNG per request by (seed, token index), never
        by batch state.
        """
        self._require("serve")
        prompts = [list(map(int, p)) for p in requests]
        if serve_cfg is not None:
            cfg = serve_cfg.replace(**serve_overrides) if serve_overrides \
                else serve_cfg
        else:
            cfg = self._serve_cfg(prompts, max_new, serve_overrides)
        eng = self._engine_for(cfg)
        return eng.generate(prompts, max_new, stream=stream,
                            sampling=sampling)

    def generate(self, prompts, max_new: int = 16, *, stream=None,
                 sampling=None, **serve_overrides):
        """One-shot convenience over :meth:`serve`: accepts one prompt (flat
        token sequence -> returns one token list) or a batch of prompts."""
        self._require("serve")
        seq = list(prompts)
        single = bool(seq) and all(isinstance(t, (int, np.integer))
                                   for t in seq)
        batch = [seq] if single else seq
        outs = self.serve(batch, max_new=max_new, stream=stream,
                          sampling=sampling, **serve_overrides)
        return outs[0] if single else outs

    def __repr__(self):
        mesh = "x".join(map(str, self.mesh_cfg.shape)) if self.mesh_cfg \
            else "single-device"
        return (f"Session({self.model.name}, mesh={mesh}, "
                f"capabilities={sorted(self.capabilities())})")


def load(arch: str, *, smoke: bool = False, mesh: MeshLike = None,
         seed: int = 0, dp_mode: Optional[str] = None,
         allreduce: Optional[str] = None, params=None,
         require: Iterable[str] = (), **overrides) -> Session:
    """The one supported entrypoint: ``load(arch) -> Session``.

    ``arch``       any registry architecture (``repro.configs.ALL_ARCHS``);
    ``smoke``      CPU-sized config variant;
    ``mesh``       ``"DxM"`` string / shape tuple / ``MeshConfig`` / None —
                   the *only* distribution knob a user script needs;
    ``dp_mode`` / ``allreduce``  training placement / reduction strategy
                   (forwarded into the MeshConfig);
    ``params``     initial parameters, a tree in the model's layout (default:
                   a seeded init); training and serving start from them;
    ``require``    capability names that must be declared *now* (e.g.
                   ``("serve",)``) — fail at load, not mid-run;
    ``overrides``  ``ModelConfig.replace`` fields (``num_layers=2``, ...).
    """
    cfg = get_config(arch, smoke=smoke)
    if overrides:
        cfg = cfg.replace(**overrides)
    sess = Session(cfg, parse_mesh(mesh), seed=seed, dp_mode=dp_mode,
                   allreduce=allreduce, params=params)
    for cap in require:
        sess._require(cap)
    return sess
