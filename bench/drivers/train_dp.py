"""Data-parallel training, steps back to back: the paper's transparent
trainer on the configuration's mesh, through ``repro.api.load(...,
mesh=..., params=...)`` and ``Session.train``.

Set-up makes the initial weights on the benchmark's side from the seed
(``bench/reference/dense.make_params``) and hands them to ``load``.  The
checked steps come first, from that state with Adam's moments at zero:
one ``Session.train`` call per step, each on that step's own global batch
(the first sequences of ``Session.train``'s own seeded synthetic stream),
so the first call initializes and broadcasts the state and compiles the
step.  A host copy of the state is taken after them.  The warm-up steps
follow, then the window opens: ``Session.train(steps=steps_per_call)`` on
its own stream runs back to back until the window's seconds have passed
(each call syncs on every step's loss, as a user's loop does); the
tokens of the steps done over the window's seconds are the cell's
``serve_tok_s``.  With ``--trace 1`` a profiler trace covers a few seconds
inside the window.
After the window the trainer is freed and the plain reference
(``bench/reference/train.py``) runs the checked steps from the same
weights and zero moments, on the same global batches, on one chip.
"""
from __future__ import annotations

import gc
import shutil
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from bench import flops_train, harness
from bench.reference import dense
from bench.reference import train as ref


# ---------------------------------------------------------------------------
# Weights and data
# ---------------------------------------------------------------------------

def initial_state(m: dict, seed: int) -> dict:
    """The state the checked steps start from, on the host in the
    reference's layout: the weights made from ``seed``, Adam's moments at
    zero, step 0."""
    import jax
    params = jax.device_get(dense.make_params(m, seed))
    return {"params": params, "m": jax.tree.map(np.zeros_like, params),
            "v": jax.tree.map(np.zeros_like, params),
            "step": np.zeros((), np.int32)}


def checked_batches(mix: dict, vocab: int, seed: int):
    """The checked steps' global batches as (tokens, labels) pairs: the
    first ``checked_steps`` x ``global_batch`` sequences of the synthetic
    stream that ``Session.train`` draws from the same seed."""
    from repro.data.readers import synthetic_tokens
    S, B = mix["seq_len"], mix["global_batch"]
    ds = synthetic_tokens(vocab, S, num_samples=mix["checked_steps"] * B,
                          seed=seed)
    return [(t, lab) for t, lab in
            zip(ds.training_data.reshape(-1, B, S),
                ds.training_labels.reshape(-1, B, S))]


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def build(cfg: dict, seed: int, params=None):
    """The configuration's model on its mesh, through ``repro.api``,
    starting from ``params`` where given."""
    from repro import api
    load = cfg["load"]
    session = api.load(load["arch"], smoke=load.get("smoke", False),
                       mesh=load["mesh"], seed=seed,
                       dp_mode=load["dp_mode"], allreduce=load["allreduce"],
                       params=params, **load.get("overrides", {}))
    check_widths(cfg["model"], session.model)
    return session


def check_widths(m: dict, model) -> None:
    """The program runs the sizes and mechanisms the configuration file
    states."""
    k = ref.dims(m)
    got = {"D": model.d_model, "H": model.num_heads,
           "KV": model.num_kv_heads, "hd": model.resolved_head_dim,
           "F": model.d_ff, "V": model.vocab_size, "L": model.num_layers,
           "theta": float(model.rope_theta), "eps": float(model.norm_eps),
           "bias": bool(model.qkv_bias),
           # a model without a rotary fraction rotates every dim
           "rot": getattr(model, "rotary_dim", model.resolved_head_dim),
           "norm": "rms" if model.norm == "rmsnorm" else "layer"}
    bad = {x: (k[x], got[x]) for x in k if k[x] != got[x]}
    if model.tie_embeddings != m["tie_word_embeddings"]:
        bad["tie"] = (m["tie_word_embeddings"], model.tie_embeddings)
    if bad:
        raise SystemExit(f"bench: the program's model differs from the "
                         f"configuration file (file, program): {bad}")


def train_kwargs(cfg: dict, mix: dict) -> dict:
    from repro.configs.base import OptimizerConfig
    return {"seq_len": mix["seq_len"], "global_batch": mix["global_batch"],
            "optimizer": OptimizerConfig(**cfg["train"]["optimizer"])}


def checked_steps(session, batches, kw: dict):
    """Run one ``Session.train`` step on each checked global batch; returns
    (host params and first moment after them, losses)."""
    import jax
    from repro.data.readers import DataSet
    # one data set per step, all kept alive: the session keys its data
    # stream on the data set's identity
    sets = [DataSet(training_data=t, training_labels=lab)
            for t, lab in batches]
    losses = [session.train(steps=1, data=ds, **kw).losses[0]
              for ds in sets]
    st = jax.device_get(session.train_state)
    return st.params, st.opt["m"], losses


def window(session, kw: dict, mix: dict, seconds: float, trace_dir):
    """Steps back to back on the session's own stream for ``seconds``;
    returns (t_open, t_end, steps, steps traced, compiles in the window)."""
    import jax
    clock = harness.CompileClock()
    compiles0 = clock.events
    k = int(mix["steps_per_call"])
    t_open = time.perf_counter()
    t_close = t_open + seconds
    trace_from = t_open + 0.3 * seconds
    trace_to = trace_from + min(float(mix.get("trace_s", 3.0)), 0.5 * seconds)
    steps, traced, tracing, done = 0, 0, None, False
    while time.perf_counter() < t_close:
        now = time.perf_counter()
        if trace_dir and not done and tracing is None and now >= trace_from:
            shutil.rmtree(trace_dir, ignore_errors=True)
            from repro.obs import profiled
            profiled().clear()
            jax.profiler.start_trace(str(trace_dir))
            tracing = jax.profiler.TraceAnnotation("bench.trace_window")
            tracing.__enter__()
        n = len(session.train(steps=k, **kw).losses)
        steps += n
        if tracing is not None:
            traced += n
            if time.perf_counter() >= trace_to:
                tracing.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing, done = None, True
    if tracing is not None:
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    t_end = time.perf_counter()
    return t_open, t_end, steps, traced, sorted(set(clock.traced[compiles0:]))


def run(cell, seed: int, seconds: float, trace: bool, root: Path,
        t_start: float, control: bool = False) -> dict:
    import jax
    cfg, mix = cell.config, cell.traffic
    before = initial_state(cfg["model"], seed)
    session = build(cfg, seed, before["params"])
    batches = checked_batches(mix, session.model.vocab_size, seed)
    kw = train_kwargs(cfg, mix)
    p_after, m_after, losses = checked_steps(session, batches, kw)
    warm = session.train(steps=mix["warmup_steps"], **kw)
    trace_dir = root / ".bench_trace" / cell.name if trace else None
    t_open, t_end, steps, traced, compiles = window(session, kw, mix,
                                                    seconds, trace_dir)
    tokens = steps * mix["global_batch"] * mix["seq_len"]
    tok_s = tokens / (t_end - t_open)
    harness.note({"train_tok_s": tok_s,
                  "step_ms": 1e3 * (t_end - t_open) / max(steps, 1),
                  "steps_in_window": steps, "window_s": t_end - t_open,
                  "checked_losses": losses, "warmup_losses": warm.losses,
                  "traced_in_window": compiles})
    device = harness.device_info(jax.devices()[:cell.chips])

    layer_ctx = None
    if trace:
        from bench import trace as tr_mod
        tr = tr_mod.read(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr_mod.busy_s(tr)
        device["window_s"] = tr_mod.window_s(tr)
        k = ref.dims(cfg["model"])
        layer_ctx = {"trace": tr, "steps_traced": traced,
                     "step_flops": flops_train.step(k, mix["seq_len"],
                                                    mix["global_batch"]),
                     "chips": cell.chips,
                     "peaks": harness.peaks_for(
                         device["kind"], strict=device["platform"] == "tpu")}
        harness.note({"device_s_by_program": tr_mod.top_modules(tr)})

    # correctness: free the program's state, then the reference
    del session
    gc.collect()
    checks, controls, got = check(cfg, before, p_after, m_after, losses,
                                  batches, control)
    harness.note({"readings": got})
    out = {"attempted": steps, "failed": 0,
           # the benchmark's tokens/s metric, here tokens trained
           "metrics": {"setup_s": t_open - t_start, "serve_tok_s": tok_s},
           "device": device,
           "checks": checks, "layer_ctx": layer_ctx}
    if control:
        out["control"] = controls
    return out


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------

def controls_of(batch: int) -> dict:
    """What each control lowers or leaves out, as ``ref.train`` arguments:
    every product in int8; rank 0's own sequence alone (the exchange left
    out); half of each batch left out."""
    shard = np.zeros(batch, np.float32)
    shard[0] = 1.0
    half = (np.arange(batch) < batch // 2).astype(np.float32)
    return {"int8": {"mm": ref.int8_mm}, "shard": {"weights": shard},
            "half": {"weights": half}}


def _norm(x) -> float:
    x = np.asarray(x, np.float32).ravel()
    return float(np.sqrt(np.dot(x, x)))


def _leaves(tree, live: dict) -> dict:
    """Leaves by key path, each cut to the entries that reach the loss."""
    import jax
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = tuple(getattr(p, "key", p) for p in path)
        x = np.asarray(x)
        out[key] = x[..., live[key]] if key in live else x
    return out


def readings(got, want, p0, live: dict) -> dict:
    """How far ``got`` = (params, first moment, losses) after the checked
    steps lies from ``want``: the widest relative gap of the losses
    (``loss_gap``), and over the leaves the worst relative gap of Adam's
    first moment (``moment_gap``) and of the update, params after less
    params before (``update_gap``); entries that do not reach the loss
    (``live``, from ``ref.live_entries``) are left out."""
    p, m, p_ref, m_ref, p0 = (_leaves(x, live) for x in
                              (got[0], got[1], want[0], want[1], p0))
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got[2], want[2]))
    moment = [_norm(m[k] - m_ref[k]) / max(_norm(m_ref[k]), 1e-30)
              for k in m]
    update = [_norm(p[k] - p_ref[k]) / max(_norm(p_ref[k] - p0[k]), 1e-30)
              for k in p]
    return {"loss_gap": float(loss_gap), "moment_gap": float(max(moment)),
            "update_gap": float(max(update))}


def check(cfg, before, p_after, m_after, losses, batches, control=False):
    """The program's readings against the fp32 reference's run of the
    same steps from the same state; with ``control`` the readings of each
    control (``controls_of``) in the program's place come back beside
    them, each run on a chip of its own where there are enough.  The
    configuration's ``limits`` name the numbers compared."""
    import jax
    m, opt = cfg["model"], cfg["train"]["optimizer"]
    live = ref.live_entries(m)
    runs = {"reference": {}}
    if control:
        runs.update(controls_of(len(batches[0][0])))
    devices = jax.devices()
    apart = len(devices) >= len(runs)
    with ThreadPoolExecutor(len(runs) if apart else 1) as pool:
        done = {name: pool.submit(ref.train, m, opt, before, batches,
                                  device=devices[i if apart else 0], **kw)
                for i, (name, kw) in enumerate(runs.items())}
        want = done.pop("reference").result()
        got = readings((p_after, m_after, losses), want, before["params"],
                       live)
        ctrl = {name: readings(f.result(), want, before["params"], live)
                for name, f in done.items()} if control else None
    return checks_of(got, cfg["limits"]), ctrl, got


def checks_of(readings: dict, limits: dict) -> dict:
    """Each number the configuration's ``limits`` name beside its limit."""
    return {k: {"value": readings[k], "limit": v} for k, v in limits.items()}


def correct(checks: dict) -> bool:
    """Every gap at or under its limit (a NaN is over)."""
    return all(c["value"] <= c["limit"] for c in checks.values())
