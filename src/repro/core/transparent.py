"""User-transparent distributed training — the paper's contribution (§III-D)
as a JAX runtime transform.

The user writes a *sequential* loss function (paper Fig. 3: the script has no
distribution code).  ``TransparentTrainer`` is the runtime: it injects

  * the Broadcast operator at initialization (§III-D.1, core/broadcast.py),
  * the gradient all-reduce after every batch   (§III-D.2, core/allreduce.py),
  * rank-sharded data ingestion                 (§III-F, repro.data),

exactly where MaTEx-TensorFlow patched the TensorFlow runtime.  Synchronous
data parallelism preserves numerical equivalence with the sequential run
(§III-E / Fig. 7) — tested in tests/test_equivalence.py.

Two placement modes:
  * ``replicated``  (paper-faithful): params replicated over DP axes inside a
    partial-manual shard_map; DP collectives are explicit and strategy-
    selectable; the "model" axis stays auto (GSPMD tensor parallelism).
  * ``fsdp``        (beyond-paper): pure pjit with 2-D parameter sharding
    (ZeRO-3 style); XLA emits all-gather/reduce-scatter pairs — the
    decomposition of the paper's allreduce.

Plus the ZeRO-1 ``reduce_scatter`` strategy: allreduce ≡ reduce-scatter +
all-gather with the optimizer update between the halves; optimizer moment
state is sharded over the DP axes as ``[dp, shard]`` arrays.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MeshConfig, RunConfig
from repro.core import allreduce as ar
from repro.core import broadcast as bc
from repro.models import common
from repro.optim.optimizers import (Optimizer, clip_by_global_norm,
                                    global_norm, make_optimizer)

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# TrainState pytree
# ---------------------------------------------------------------------------

@dataclass
class TrainState:
    params: Any
    opt: Any
    err: Any          # error-feedback tree (compressed strategy) or None
    step: Any


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt, s.err, s.step), None),
    lambda aux, ch: TrainState(*ch))


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def batch_pspec(leaf, dp_axes: Tuple[str, ...]) -> P:
    """Shard dim 0 (batch) over the DP axes, replicate the rest."""
    return P(tuple(dp_axes), *([None] * (leaf.ndim - 1)))


def _batch_specs_tree(batch_like, dp_axes):
    return jax.tree.map(lambda l: batch_pspec(l, dp_axes), batch_like)


def _flatten_to_vec(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])


def _unflatten_from_vec(vec, tree):
    leaves, treedef = jax.tree.flatten(tree)
    out, off = [], 0
    for l in leaves:
        out.append(vec[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def _linear_dp_rank(axes: Tuple[str, ...]):
    r = jnp.zeros((), jnp.int32)
    for a in axes:
        r = r * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return r


@jax.named_scope("allreduce")
def _scatter_mean_vec(vec, axes: Tuple[str, ...], pad_to: int, dp: int):
    """reduce-scatter(mean) of a flat fp32 vector -> local [pad_to/dp] shard.
    Scattering over each axis in turn composes the sum in linear-rank
    order (see ``_linear_dp_rank``)."""
    v = jnp.pad(vec, (0, pad_to - vec.size))
    for a in axes:
        v = jax.lax.psum_scatter(v, a, scatter_dimension=0, tiled=True)
    return v / dp


@jax.named_scope("allreduce")
def _gather_vec(shard, axes: Tuple[str, ...]):
    """Inverse of ``_scatter_mean_vec``'s scatter: the full flat vector."""
    for a in reversed(axes):
        shard = jax.lax.all_gather(shard, a, axis=0, tiled=True)
    return shard


def _local_param_shard(params, axes, pad_to: int, dp: int):
    """This rank's slice of the flat parameter vector (no communication)."""
    vec = _flatten_to_vec(params)
    vec = jnp.pad(vec, (0, pad_to - vec.size))
    shard_size = pad_to // dp
    r = _linear_dp_rank(axes)
    return jax.lax.dynamic_slice(vec, (r * shard_size,), (shard_size,))


def _num_microbatches(run_cfg: RunConfig, local_batch: int) -> int:
    mb = run_cfg.microbatch
    if mb <= 0 or mb >= local_batch:
        return 1
    assert local_batch % mb == 0, (local_batch, mb)
    return local_batch // mb


# ---------------------------------------------------------------------------
# The transparent primitive: drop-in value_and_grad with injected reduction
# ---------------------------------------------------------------------------

def value_and_grad(loss_fn, *, strategy: str = "layerwise",
                   axes: Tuple[str, ...] = ("data",), bucket_bytes: int = 32 << 20):
    """jax.value_and_grad drop-in that all-reduces gradients over the DP axes.

    For users writing custom loops inside a shard_map manual region — the
    same injection the paper performs in the TF runtime."""
    vg = jax.value_and_grad(loss_fn)

    def wrapped(params, *args, **kw):
        loss, grads = vg(params, *args, **kw)
        grads, _ = ar.reduce_gradients(grads, strategy, axes, bucket_bytes)
        return (jax.lax.pmean(loss, tuple(axes)) if axes else loss), grads

    return wrapped


# ---------------------------------------------------------------------------
# TransparentTrainer
# ---------------------------------------------------------------------------

class TransparentTrainer:
    """Runtime that turns a sequential loss_fn into synchronous DP training.

    loss_fn(params, batch) -> scalar; param_specs: ParamSpec tree.
    """

    @classmethod
    def from_bundle(cls, run_cfg: RunConfig, bundle, *, mesh=None,
                    optimizer: Optional[Optimizer] = None):
        """Session-owned construction (repro.api): a trainer straight from a
        registry ``ModelBundle`` — the bundle's ``TrainStepContract`` loss
        and ParamSpec tree, no hand-wiring of either at call sites."""
        return cls(run_cfg, bundle.loss_fn, bundle.specs, mesh=mesh,
                   optimizer=optimizer)

    def __init__(self, run_cfg: RunConfig, loss_fn: Callable, param_specs,
                 mesh=None, optimizer: Optional[Optimizer] = None):
        from repro.launch.mesh import build_mesh
        run_cfg.validate()
        self.run_cfg = run_cfg
        self.mesh_cfg = run_cfg.mesh
        self.mesh = mesh if mesh is not None else build_mesh(run_cfg.mesh)
        self.loss_fn = loss_fn
        self.param_specs = param_specs
        self.opt = optimizer or make_optimizer(run_cfg.optimizer)
        self.rules = common.rules_for(self.mesh_cfg, run_cfg.model)
        self.dp_axes = tuple(a for a in self.mesh_cfg.axis_names
                             if a in ("pod", "data"))
        self.dp = int(np.prod([s for s, a in zip(self.mesh_cfg.shape,
                                                 self.mesh_cfg.axis_names)
                               if a in ("pod", "data")])) or 1
        # The paper-faithful manual region is manual over the DP axes and
        # keeps the "model" axis auto (GSPMD tensor parallelism).
        if self.mesh_cfg.dp_mode == "replicated" and self.dp_axes:
            self._manual_axes = set(self.dp_axes)
        else:
            self._manual_axes = None
        self._zero1 = (self.mesh_cfg.allreduce == "reduce_scatter"
                       and self._manual_axes is not None)
        n_params = sum(int(np.prod(s.shape))
                       for s in common.spec_leaves(param_specs))
        self._n_params = n_params
        self._padded = -(-n_params // self.dp) * self.dp
        if self._zero1 and self._padded >= 2 ** 31:
            raise ValueError(
                f"zero1 flat-shard state ({n_params/1e9:.1f}B params) exceeds "
                "int32 dynamic-slice indexing — and replicated fp32 masters "
                "cannot fit HBM at this scale anyway; use dp_mode='fsdp'")
        self._step_cache: Dict[Any, Callable] = {}

    # -- structure builders ---------------------------------------------------

    def _opt_struct(self):
        """abstract opt-state structure (global shapes)."""
        pstructs = common.param_shape_structs(self.param_specs)
        if self._zero1:
            shard = self._padded // self.dp
            vec = jax.ShapeDtypeStruct((self.dp, shard), jnp.float32)
            return jax.eval_shape(self.opt.init,
                                  {"flat": jax.ShapeDtypeStruct((self.dp, shard),
                                                                jnp.float32)})
        return jax.eval_shape(self.opt.init, pstructs)

    def _opt_manual_specs(self):
        """shard_map in/out specs for the optimizer state."""
        struct = self._opt_struct()
        if self._zero1:
            dp_tuple = tuple(self.dp_axes)
            return jax.tree.map(
                lambda l: P(dp_tuple, None) if l.ndim == 2 else P(), struct)
        return jax.tree.map(lambda _: P(), struct)

    def param_shardings(self):
        return common.logical_to_mesh(self.param_specs, self.mesh, self.rules)

    def _param_manual_specs(self):
        return common.manual_axis_specs(self.param_specs, self.rules,
                                        self.dp_axes)

    def _ns(self, spec: P):
        return jax.sharding.NamedSharding(self.mesh, spec)

    def state_shardings(self):
        ps = self.param_shardings()
        rep = self._ns(P())
        if self._zero1:
            opt_sh = jax.tree.map(
                lambda l: self._ns(P(tuple(self.dp_axes), None))
                if l.ndim == 2 else rep, self._opt_struct())
        else:
            # optimizer moments mirror parameter shardings (matched by shape)
            pshapes = {}
            for l, s in zip(common.spec_leaves(self.param_specs),
                            jax.tree.leaves(ps)):
                pshapes.setdefault(tuple(l.shape), s)
            opt_sh = jax.tree.map(
                lambda l: pshapes.get(tuple(l.shape), rep), self._opt_struct())
        err_sh = (jax.tree.map(lambda s: s, ps)
                  if self.mesh_cfg.allreduce == "compressed" else None)
        return TrainState(params=ps, opt=opt_sh, err=err_sh, step=rep)

    def state_structs(self):
        """ShapeDtypeStructs (with shardings) for the dry-run."""
        pstructs = common.param_shape_structs(self.param_specs)
        err = (jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pstructs)
            if self.mesh_cfg.allreduce == "compressed" else None)
        structs = TrainState(params=pstructs, opt=self._opt_struct(), err=err,
                             step=jax.ShapeDtypeStruct((), jnp.int32))
        return jax.tree.map(
            lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype, sharding=sh),
            structs, self.state_shardings())

    # -- init ------------------------------------------------------------------

    def init(self, seed: int = 0, params=None):
        """Materialize a broadcast-consistent TrainState on the mesh, from
        ``params`` (a tree in the model's layout) where given, else from a
        seeded init."""
        mesh_cfg = self.mesh_cfg
        if params is not None:
            params = jax.tree.map(
                lambda x, s: jnp.asarray(x, s.dtype), params,
                common.param_shape_structs(self.param_specs))

        def _base_state(key, given):
            params = (common.init_params(self.param_specs, key)
                      if given is None else given)
            err = (ar.init_error_tree(params)
                   if mesh_cfg.allreduce == "compressed" else None)
            return params, err

        if self._manual_axes is not None:
            pspecs = self._param_manual_specs()
            opt_specs = self._opt_manual_specs()
            err_specs = (jax.tree.map(lambda s: s, pspecs)
                         if mesh_cfg.allreduce == "compressed" else None)

            def _init_inner(key, given):
                params, err = _base_state(key, given)
                # paper §III-D.1: rank-0 broadcast guarantees identical replicas
                params = bc.broadcast_from_rank0(params, self.dp_axes)
                if self._zero1:
                    shard = _local_param_shard(params, self.dp_axes,
                                               self._padded, self.dp)
                    opt = self.opt.init({"flat": shard[None, :]})
                else:
                    opt = self.opt.init(params)
                return TrainState(params=params, opt=opt, err=err,
                                  step=jnp.zeros((), jnp.int32))

            smapped = jax.shard_map(
                _init_inner, mesh=self.mesh,
                in_specs=(P(), P()),
                out_specs=TrainState(params=pspecs, opt=opt_specs,
                                     err=err_specs, step=P()),
                check_vma=False, axis_names=self._manual_axes)
            fn = jax.jit(smapped, out_shardings=self.state_shardings())
        else:
            def _init_auto(key, given):
                params, err = _base_state(key, given)
                return TrainState(params=params, opt=self.opt.init(params),
                                  err=err, step=jnp.zeros((), jnp.int32))
            fn = jax.jit(_init_auto, out_shardings=self.state_shardings())
        return fn(jax.random.PRNGKey(seed), params)

    # -- the transparent step ----------------------------------------------------

    def _grads_of(self, params, batch):
        loss, g = jax.value_and_grad(self.loss_fn)(params, batch)
        return loss, jax.tree.map(lambda x: x.astype(jnp.float32), g)

    def _accumulate(self, state, batch):
        local_b = jax.tree.leaves(batch)[0].shape[0]
        n_micro = _num_microbatches(self.run_cfg, local_b)
        if n_micro == 1:
            return self._grads_of(state.params, batch)
        mb = local_b // n_micro
        stacked = jax.tree.map(
            lambda x: x.reshape((n_micro, mb) + x.shape[1:]), batch)

        def acc_body(carry, micro):
            acc, loss_acc = carry
            loss, g = self._grads_of(state.params, micro)
            return (jax.tree.map(jnp.add, acc, g), loss_acc + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             state.params)
        (grads, loss), _ = jax.lax.scan(
            acc_body, (zeros, jnp.zeros((), jnp.float32)), stacked)
        return loss / n_micro, jax.tree.map(lambda g: g / n_micro, grads)

    def _local_step(self, state: TrainState, batch):
        """Single-replica semantics + injected collectives (manual region)."""
        run_cfg, mesh_cfg = self.run_cfg, self.mesh_cfg
        loss, grads = self._accumulate(state, batch)
        new_err = state.err

        if self._zero1:
            # ZeRO-1: RS(mean) + sharded optimizer + AG (beyond-paper)
            vec = _flatten_to_vec(grads)
            gshard = _scatter_mean_vec(vec, self.dp_axes, self._padded,
                                       self.dp)
            sq = jax.lax.psum(jnp.sum(jnp.square(gshard)), tuple(self.dp_axes))
            gn = jnp.sqrt(sq)
            if run_cfg.optimizer.grad_clip:
                gshard = gshard * jnp.minimum(
                    1.0, run_cfg.optimizer.grad_clip / jnp.maximum(gn, 1e-12))
            pshard = _local_param_shard(state.params, self.dp_axes,
                                        self._padded, self.dp)
            new_pshard, new_opt = self.opt.update(
                {"flat": gshard[None, :]}, state.opt, {"flat": pshard[None, :]})
            new_vec = _gather_vec(new_pshard["flat"][0], self.dp_axes)
            new_params = _unflatten_from_vec(new_vec[:self._n_params],
                                             state.params)
        else:
            grads, new_err = ar.reduce_gradients(
                grads, mesh_cfg.allreduce, self.dp_axes,
                mesh_cfg.bucket_bytes, state.err)
            if run_cfg.optimizer.grad_clip:
                grads, gn = clip_by_global_norm(grads,
                                                run_cfg.optimizer.grad_clip)
            else:
                gn = global_norm(grads)
            new_params, new_opt = self.opt.update(grads, state.opt,
                                                  state.params)

        if self.dp_axes:
            loss = jax.lax.pmean(loss, tuple(self.dp_axes))
        new_state = TrainState(params=new_params, opt=new_opt, err=new_err,
                               step=state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gn,
                           "step": new_state.step}

    def _build_step(self, batch_like):
        mesh_cfg = self.mesh_cfg
        state_sh = self.state_shardings()
        batch_sh = jax.tree.map(
            lambda l: self._ns(batch_pspec(l, self.dp_axes)), batch_like)

        if self._manual_axes is not None:
            state_specs = TrainState(
                params=self._param_manual_specs(),
                opt=self._opt_manual_specs(),
                err=(jax.tree.map(lambda s: s, self._param_manual_specs())
                     if mesh_cfg.allreduce == "compressed" else None),
                step=P())
            bspecs = _batch_specs_tree(batch_like, self.dp_axes)
            metric_specs = {"loss": P(), "grad_norm": P(), "step": P()}
            smapped = jax.shard_map(
                self._local_step, mesh=self.mesh,
                in_specs=(state_specs, bspecs),
                out_specs=(state_specs, metric_specs),
                check_vma=False, axis_names=self._manual_axes)
            fn = jax.jit(smapped, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        else:
            # fsdp / auto mode: XLA derives reduce-scatter/all-gather from the
            # 2-D parameter sharding (beyond-paper ZeRO-3)
            def auto_step(state, batch):
                with common.activation_batch_axes(self.dp_axes):
                    loss, grads = self._accumulate(state, batch)
                if self.run_cfg.optimizer.grad_clip:
                    grads, gn = clip_by_global_norm(
                        grads, self.run_cfg.optimizer.grad_clip)
                else:
                    gn = global_norm(grads)
                params, opt = self.opt.update(grads, state.opt, state.params)
                new_state = TrainState(params=params, opt=opt, err=state.err,
                                       step=state.step + 1)
                return new_state, {"loss": loss, "grad_norm": gn,
                                   "step": new_state.step}

            fn = jax.jit(auto_step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        return fn

    def step_fn(self, batch_like):
        """Compiled train step for batches shaped like ``batch_like``."""
        key = tuple(sorted(
            (jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
            for k, v in jax.tree_util.tree_leaves_with_path(batch_like)))
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(batch_like)
        return self._step_cache[key]

    def step(self, state, batch):
        return self.step_fn(batch)(state, batch)

    # -- lowering hook for the dry-run -----------------------------------------

    def lower_step(self, batch_structs):
        """lower() the train step against ShapeDtypeStructs (no allocation)."""
        batch_structs = jax.tree.map(
            lambda st: jax.ShapeDtypeStruct(
                st.shape, st.dtype,
                sharding=self._ns(batch_pspec(st, self.dp_axes))),
            batch_structs)
        return self.step_fn(batch_structs).lower(self.state_structs(),
                                                 batch_structs)
