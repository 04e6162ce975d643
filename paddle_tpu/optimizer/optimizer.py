"""Optimizers (reference: python/paddle/optimizer/optimizer.py + per-algo files).

TPU-idiomatic: step() performs ONE fused pytree update — all params/grads/states are
updated inside a single cached XLA executable (the reference's multi_tensor path is the
analog, optimizer.py _append_optimize_multi_tensor_op). Learning rate is passed as a
device scalar so LR schedules never trigger recompilation.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import no_grad
from ..core.dtype import convert_dtype
from ..core.tensor import Parameter, Tensor
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax", "Adagrad",
           "Adadelta", "RMSProp", "Lamb"]


def _const_at(shape, dtype, value, sh):
    """Constant buffer born at placement ``sh``: each addressable device
    materializes ONLY its own shard. Neither a per-buffer jit (one tiny
    compile per param per state) nor ``jnp.full`` + ``device_put`` (stages
    the full array on one device first — the transient allocation the ZeRO
    placement hook exists to avoid)."""
    import numpy as np

    def _shard(index):
        sub = tuple(len(range(*sl.indices(dim)))
                    for sl, dim in zip(index, shape))
        return np.full(sub, value, np.dtype(dtype))

    try:
        return jax.make_array_from_callback(tuple(shape), sh, _shard)
    except Exception:
        # e.g. a memory-kind the callback path can't target (ZeRO offload):
        # host-stage the full array and let device_put scatter the shards
        return jax.device_put(np.full(tuple(shape), value, np.dtype(dtype)),
                              sh)


@functools.lru_cache(maxsize=None)
def _jitted_update(cls, static_key):
    """One compiled update over the whole parameter pytree per optimizer config.

    Params and accumulator states are DONATED: the update is elementwise, so
    XLA writes new values into the incoming buffers instead of allocating a
    second params+2·moments footprint per step — on the eager path that
    transient was the largest allocation of the whole step (the compiled
    TrainStep has donated these since PR 1). ``_step_group`` replaces
    ``p._data`` / the accumulator dicts wholesale right after the call, so
    the invalidated inputs are dead on arrival; the visible hazard is the
    same one the sparse path documents — an array handle taken BEFORE the
    step (``p.value()``, an old ``state_dict()``) is no longer readable
    after it; holders should ``.copy()`` or snapshot to host first
    (``AsyncCheckpointer`` already does). Grads are NOT donated:
    ``p._grad`` stays readable after ``step()`` until ``clear_grad()``."""
    static = dict(static_key)

    def update(params, grads, states, scalars):
        new_params, new_states = cls._update_rule(params, grads, states, scalars,
                                                  **static)
        return new_params, new_states

    return jax.jit(update, donate_argnums=(0, 2))


@functools.lru_cache(maxsize=None)
def _jitted_sparse_update(cls, static_key, donate: bool):
    """Compiled row-wise (SelectedRows) update. When `donate`, the PARAM
    buffer is donated so the scatter aliases it in place and a [V, d]
    embedding update never allocates a second V·d buffer (reference
    phi/kernels/selected_rows/ kernels mutate in place). Accumulator state
    and master weights are NOT donated — optimizer.state_dict() snapshots
    alias those buffers and must stay readable. Donation means a user-held
    `p.value()` array from before the step becomes invalid; holders should
    `.copy()` (same hazard as the reference's in-place mutation)."""
    static = dict(static_key)

    def update(param, rows, vals, state, scalars):
        return cls._sparse_update_rule(param, rows, vals, state, scalars,
                                       **static)

    return jax.jit(update, donate_argnums=(0,) if donate else ())


class Optimizer:
    _state_names: List[str] = []

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False):
        if parameters is None:
            raise ValueError("parameters must be provided (eager mode, like reference "
                             "dygraph optimizers)")
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        if isinstance(weight_decay, (float, int)) or weight_decay is None:
            self._weight_decay = float(weight_decay or 0.0)
        else:  # L2Decay-style object with a coeff
            from ..regularizer import L1Decay
            if isinstance(weight_decay, L1Decay):
                raise NotImplementedError(
                    "optimizers apply decoupled L2 weight decay; add an L1 "
                    "penalty to the loss (or regularizer(param) to grads) "
                    "manually")
            self._weight_decay = float(getattr(weight_decay, "_coeff",
                                               getattr(weight_decay, "coeff", 0.0)))
        self._accumulators: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._master_weights: Dict[int, jnp.ndarray] = {}
        self._step_count = 0
        # ZeRO hook (DygraphShardingOptimizer._place_states installs it):
        # maps (param, state_name, shape) -> Sharding so moment/master buffers
        # are BORN shard-sized — a replicated zeros + device_put would briefly
        # hold the full-size buffer on one device, which for billion-param
        # models is exactly the allocation ZeRO exists to avoid
        self._state_placement_fn = None

    # ------------------------------------------------------------ lr plumbing

    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value: float):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler: LRScheduler):
        self._learning_rate = scheduler

    # ------------------------------------------------------------ state

    def _ensure_state(self, p: Parameter):
        pid = id(p)
        if pid not in self._accumulators:
            dtype = jnp.float32 if self._multi_precision else p.value().dtype
            shape = tuple(p.shape)
            self._accumulators[pid] = {
                name: self._new_state(p, name, shape, dtype)
                for name in self._state_names}
            if self._multi_precision and p.value().dtype != jnp.float32:
                self._master_weights[pid] = self._new_master(p)
        return self._accumulators[pid]

    def _new_state(self, p: Parameter, name: str, shape, dtype):
        """A fresh state buffer, created directly at its ZeRO shard placement
        when a placement hook is installed (each device materializes only its
        1/world_size shard — no transient full-size buffer)."""
        place = self._state_placement_fn
        sh = place(p, name, shape) if place is not None else None
        if sh is None:
            return jnp.zeros(shape, dtype)
        return _const_at(shape, dtype, 0.0, sh)

    def _new_master(self, p: Parameter):
        """fp32 master copy of a low-precision param; born shard-sized under
        ZeRO (the cast writes straight into the sharded layout)."""
        place = self._state_placement_fn
        sh = place(p, "master", tuple(p.shape)) if place is not None else None
        if sh is None:
            return p.value().astype(jnp.float32)
        # reshard the LOW-precision param first (half the bytes), then cast
        # eagerly — the elementwise cast inherits the shard placement, with
        # no per-param jit compile and no full-size fp32 transient
        return jax.device_put(p.value(), sh).astype(jnp.float32)

    def _ensure_all_states(self):
        """Materialize state for every trainable param (used by ZeRO placement)."""
        for p in self._parameter_list:
            if p.trainable:
                self._ensure_state(p)

    def _static_config(self):
        return (("weight_decay", self._weight_decay),)

    def _wd_scale(self, p: Parameter) -> float:
        """Per-param weight-decay multiplier (AdamW/Lamb exclusion hooks)."""
        return 1.0

    def _scalars(self, lr):
        self._step_count += 1
        from ..core.lazy import scalar_const
        # lr values repeat across steps (cached device constants — an uncached
        # scalar host→device transfer costs milliseconds on a TPU host); the
        # step counter changes every call, so keep it on device and bump it
        # there
        dev = getattr(self, "_step_dev", None)
        if dev is not None and getattr(self, "_step_dev_count", None) \
                == self._step_count - 1:
            step = dev + 1.0
        else:  # first step, or _step_count was reset (state_dict load)
            step = jnp.asarray(float(self._step_count), jnp.float32)
        self._step_dev = step
        self._step_dev_count = self._step_count
        return {"lr": scalar_const(float(lr)).astype(jnp.float32),
                "step": step}

    def _rollback_step(self):
        """Un-advance the per-step scalars after a compiled step whose update
        was discarded on device (AMP found-inf skip): the next step must
        reuse this step number for bias correction, matching the eager path
        where ``scaler.step`` never calls ``optimizer.step``."""
        self._step_count = max(self._step_count - 1, 0)
        self._step_dev = None
        self._step_dev_count = None

    # ------------------------------------------------------------ step

    @no_grad()
    def step(self):
        from ..core.selected_rows import SelectedRows

        params = [p for p in self._parameter_list
                  if p.trainable and p._grad is not None]
        if not params:
            return
        # deferred-eager boundary: concretizing the first grad flushes the whole
        # pending fwd+bwd stream as ONE fused executable; the rest are ready
        from ..core.lazy import concrete

        def _conc(g):
            if isinstance(g, SelectedRows):
                g.rows = concrete(g.rows)
                g.values = concrete(g.values)
                return g
            return concrete(g)

        grads = [_conc(p._grad) for p in params]
        if self._grad_clip is not None:
            clipped = self._grad_clip(list(zip(params, grads)))
            grads = [g for _, g in clipped]
        for p in params:
            self._ensure_state(p)

        scalars = self._scalars(self.get_lr())  # advances step count ONCE

        # SelectedRows grads (sparse embeddings) take the row-wise path;
        # everything else goes through the fused dense update below
        sparse_pairs = [(p, g) for p, g in zip(params, grads)
                        if isinstance(g, SelectedRows)]
        if sparse_pairs:
            dense_pairs = [(p, g) for p, g in zip(params, grads)
                           if not isinstance(g, SelectedRows)]
            for p, sr in sparse_pairs:
                self._sparse_apply(p, sr, scalars)
            if not dense_pairs:
                return
            params = [p for p, _ in dense_pairs]
            grads = [g for _, g in dense_pairs]
        # pipeline parallelism places stages on disjoint submeshes; one jit cannot
        # span disjoint device sets, so run one fused update per device group
        groups = {}
        for p, g in zip(params, grads):
            try:
                key = frozenset(p.value().sharding.device_set)
            except Exception:
                key = None
            groups.setdefault(key, []).append((p, g))
        if len(groups) > 1:
            for pairs in groups.values():
                self._step_group([p for p, _ in pairs], [g for _, g in pairs],
                                 scalars)
            return
        self._step_group(params, grads, scalars)

    def _step_group(self, params, grads, scalars):
        use_master = [id(p) in self._master_weights for p in params]
        param_vals = [self._master_weights[id(p)] if m else p.value()
                      for p, m in zip(params, use_master)]
        # per-param lr scale (ParamAttr learning_rate)
        lr_scales = tuple(float(p.optimize_attr.get("learning_rate", 1.0))
                          for p in params)
        wd_scales = tuple(self._wd_scale(p) for p in params)
        states = [self._accumulators[id(p)] for p in params]

        static_key = self._static_config() + (("lr_scales", lr_scales),
                                              ("wd_scales", wd_scales))
        new_params, new_states = _jitted_update(type(self), static_key)(
            param_vals,
            [g if g.dtype == v.dtype else g.astype(v.dtype)
             for g, v in zip(grads, param_vals)],
            states, scalars)

        for p, newv, news, m in zip(params, new_params, new_states, use_master):
            if m:
                self._master_weights[id(p)] = newv
                p._set_value_inplace(newv.astype(p.value().dtype))
            else:
                p._set_value_inplace(newv)
            self._accumulators[id(p)] = news

    @no_grad()
    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    # ------------------------------------------------------------ checkpoint

    def _param_keys(self):
        """Checkpoint keys for _parameter_list. Layer-assigned names are NOT
        unique across layers ('linear.weight' twice in a 2-Linear net), and a
        colliding key silently cross-wires moment tensors between parameters
        on restore — so duplicated names get an __<index> disambiguator.
        Unique names keep their bare key (old snapshots stay loadable)."""
        from collections import Counter
        names = [p.name or f"param_{i}"
                 for i, p in enumerate(self._parameter_list)]
        counts = Counter(names)
        return [f"{n}__{i}" if counts[n] > 1 else n
                for i, n in enumerate(names)]

    def state_dict(self):
        """Snapshot BY REFERENCE: the returned Tensors wrap the live moment/
        master arrays. The dense compiled update donates those buffers
        (see _jitted_update), so a snapshot taken before a later ``step()``
        is no longer readable afterwards — serialize (``paddle.save``,
        ``np.asarray``) or ``.copy()`` before stepping if you need it to
        outlive the step. ``AsyncCheckpointer`` already host-copies at
        ``save()`` time."""
        out = {"master_weights": {}, "LR_Scheduler": {}}
        for p, key in zip(self._parameter_list, self._param_keys()):
            pid = id(p)
            if pid in self._accumulators:
                for name, arr in self._accumulators[pid].items():
                    out[f"{key}_{name}"] = Tensor(arr)
            if pid in self._master_weights:
                out["master_weights"][key] = Tensor(self._master_weights[pid])
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        out["_step_count"] = self._step_count
        return out

    def set_state_dict(self, state):
        for p, key in zip(self._parameter_list, self._param_keys()):
            acc = {}
            for name in self._state_names:
                k = f"{key}_{name}"
                if k in state:
                    v = state[k]
                    acc[name] = v.value() if isinstance(v, Tensor) else jnp.asarray(v)
            if acc:
                self._accumulators[id(p)] = acc
            mw = state.get("master_weights", {})
            if key in mw:
                v = mw[key]
                self._master_weights[id(p)] = (v.value() if isinstance(v, Tensor)
                                               else jnp.asarray(v))
        if isinstance(self._learning_rate, LRScheduler) and state.get("LR_Scheduler"):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        self._step_count = state.get("_step_count", 0)

    # subclasses implement:
    @staticmethod
    def _update_rule(params, grads, states, scalars, **static):
        raise NotImplementedError

    # ------------------------------------------------------------ sparse

    def _sparse_apply(self, p, sr, scalars):
        """Row-wise update for a SelectedRows gradient (reference
        selected_rows optimizer kernels / Adam lazy_mode). Regularization is
        skipped, matching the reference's warning for sparse parameters."""
        import warnings

        if self._weight_decay and not getattr(self, "_warned_sparse_wd", False):
            warnings.warn(
                "weight decay is skipped for parameters with SelectedRows "
                "(sparse) gradients — the reference applies no "
                "regularization on the sparse path either")
            self._warned_sparse_wd = True
        sr = sr.merge()     # no-op when the grad clip already merged
        lr_scale = float(p.optimize_attr.get("learning_rate", 1.0))
        use_master = id(p) in self._master_weights
        pv = self._master_weights[id(p)] if use_master else p.value()
        state = self._accumulators[id(p)]
        key = self._static_config() + (("lr_scale", lr_scale),)
        # master weights live in state_dict snapshots: don't donate them
        new_p, new_state = _jitted_sparse_update(type(self), key,
                                                 not use_master)(
            pv, sr.rows, sr.values.astype(pv.dtype), state, scalars)
        self._accumulators[id(p)] = new_state
        if use_master:
            self._master_weights[id(p)] = new_p
            p._set_value_inplace(new_p.astype(p.value().dtype))
        else:
            p._set_value_inplace(new_p)

    @staticmethod
    def _sparse_update_rule(param, rows, vals, state, scalars, **static):
        raise NotImplementedError(
            "this optimizer has no SelectedRows update rule; use "
            "SGD/Momentum/Adam/AdamW/Adagrad for sparse-grad embeddings or "
            "set sparse=False (reference supports the same subset)")


def _apply_wd(p, g, wd):
    """L2 regularization added to the gradient (reference L2Decay semantics)."""
    return g + wd * p if wd else g


class SGD(Optimizer):
    _state_names: List[str] = []

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, lr_scales=(),
                     wd_scales=()):
        lr = scalars["lr"]
        new_params = [p - (lr * s) * _apply_wd(p, g, weight_decay * w)
                      for p, g, s, w in zip(params, grads, lr_scales, wd_scales)]
        return new_params, states

    @staticmethod
    def _sparse_update_rule(param, rows, vals, state, scalars, weight_decay=0.0,
                            lr_scale=1.0):
        # reference sgd selected-rows kernel: scatter-subtract touched rows
        return param.at[rows].add(-(scalars["lr"] * lr_scale) * vals), state


class Momentum(Optimizer):
    _state_names = ["velocity"]

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None,
                 multi_precision=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name,
                         multi_precision)
        self._momentum = float(momentum)
        self._use_nesterov = bool(use_nesterov)

    def _static_config(self):
        return super()._static_config() + (("momentum", self._momentum),
                                           ("use_nesterov", self._use_nesterov))

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, momentum=0.9,
                     use_nesterov=False, lr_scales=(), wd_scales=()):
        lr = scalars["lr"]
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            g = _apply_wd(p, g, weight_decay * w)
            v = momentum * st["velocity"] + g
            if use_nesterov:
                p2 = p - (lr * s) * (g + momentum * v)
            else:
                p2 = p - (lr * s) * v
            new_params.append(p2)
            new_states.append({"velocity": v})
        return new_params, new_states

    @staticmethod
    def _sparse_update_rule(param, rows, vals, state, scalars, weight_decay=0.0,
                            momentum=0.9, use_nesterov=False, lr_scale=1.0):
        # lazy rows-only velocity (reference sparse_momentum semantics:
        # untouched rows keep their velocity unchanged this step)
        lr = scalars["lr"] * lr_scale
        v_rows = momentum * state["velocity"][rows] + vals
        if use_nesterov:
            delta = lr * (vals + momentum * v_rows)
        else:
            delta = lr * v_rows
        return (param.at[rows].add(-delta),
                {"velocity": state["velocity"].at[rows].set(v_rows)})


class Adam(Optimizer):
    _state_names = ["moment1", "moment2"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None,
                 lazy_mode=False, multi_precision=False, use_multi_tensor=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name,
                         multi_precision)
        self._beta1 = float(beta1 if not isinstance(beta1, Tensor) else beta1.item())
        self._beta2 = float(beta2 if not isinstance(beta2, Tensor) else beta2.item())
        self._epsilon = float(epsilon)

    def _static_config(self):
        return super()._static_config() + (("beta1", self._beta1),
                                           ("beta2", self._beta2),
                                           ("epsilon", self._epsilon))

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, beta1=0.9,
                     beta2=0.999, epsilon=1e-8, lr_scales=(), wd_scales=(),
                     decouple_wd=False):
        lr = scalars["lr"]
        t = scalars["step"]
        bc1 = 1.0 - beta1 ** t
        bc2 = 1.0 - beta2 ** t
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            if not decouple_wd:
                g = _apply_wd(p, g, weight_decay * w)
            m1 = beta1 * st["moment1"] + (1 - beta1) * g
            m2 = beta2 * st["moment2"] + (1 - beta2) * jnp.square(g)
            m1h = m1 / bc1
            m2h = m2 / bc2
            step_v = (lr * s) * m1h / (jnp.sqrt(m2h) + epsilon)
            if decouple_wd and weight_decay * w:
                step_v = step_v + (lr * s) * (weight_decay * w) * p
            new_params.append(p - step_v)
            new_states.append({"moment1": m1, "moment2": m2})
        return new_params, new_states

    @staticmethod
    def _sparse_update_rule(param, rows, vals, state, scalars, weight_decay=0.0,
                            beta1=0.9, beta2=0.999, epsilon=1e-8, lr_scale=1.0,
                            decouple_wd=False):
        # reference Adam lazy_mode over SelectedRows: moments and param move
        # only at touched rows; bias correction uses the global step
        lr = scalars["lr"] * lr_scale
        t = scalars["step"]
        m1r = beta1 * state["moment1"][rows] + (1 - beta1) * vals
        m2r = beta2 * state["moment2"][rows] + (1 - beta2) * jnp.square(vals)
        m1h = m1r / (1.0 - beta1 ** t)
        m2h = m2r / (1.0 - beta2 ** t)
        delta = lr * m1h / (jnp.sqrt(m2h) + epsilon)
        return (param.at[rows].add(-delta),
                {"moment1": state["moment1"].at[rows].set(m1r),
                 "moment2": state["moment2"].at[rows].set(m2r)})


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py,
    default coeff 0.01)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, name=None,
                 lazy_mode=False, multi_precision=False):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, name, lazy_mode, multi_precision)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _static_config(self):
        return super()._static_config() + (("decouple_wd", True),)

    def _wd_scale(self, p):
        if (self._apply_decay_param_fun is not None
                and not self._apply_decay_param_fun(p.name)):
            return 0.0
        return 1.0


class Adamax(Optimizer):
    _state_names = ["moment", "inf_norm"]

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = float(beta1), float(beta2), float(epsilon)

    def _static_config(self):
        return super()._static_config() + (("beta1", self._beta1),
                                           ("beta2", self._beta2),
                                           ("epsilon", self._epsilon))

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, beta1=0.9,
                     beta2=0.999, epsilon=1e-8, lr_scales=(), wd_scales=()):
        lr = scalars["lr"]
        t = scalars["step"]
        bc1 = 1.0 - beta1 ** t
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            g = _apply_wd(p, g, weight_decay * w)
            m = beta1 * st["moment"] + (1 - beta1) * g
            u = jnp.maximum(beta2 * st["inf_norm"], jnp.abs(g))
            new_params.append(p - (lr * s) / bc1 * m / (u + epsilon))
            new_states.append({"moment": m, "inf_norm": u})
        return new_params, new_states


class Adagrad(Optimizer):
    _state_names = ["moment"]

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = float(epsilon)
        self._init_acc = float(initial_accumulator_value)

    def _ensure_state(self, p):
        pid = id(p)
        if pid not in self._accumulators:
            shape, dtype = tuple(p.shape), p.value().dtype
            place = self._state_placement_fn
            sh = place(p, "moment", shape) if place is not None else None
            if sh is None:
                moment = jnp.full(shape, self._init_acc, dtype)
            else:
                moment = _const_at(shape, dtype, self._init_acc, sh)
            self._accumulators[pid] = {"moment": moment}
        return self._accumulators[pid]

    def _static_config(self):
        return super()._static_config() + (("epsilon", self._epsilon),)

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, epsilon=1e-6,
                     lr_scales=(), wd_scales=()):
        lr = scalars["lr"]
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            g = _apply_wd(p, g, weight_decay * w)
            m = st["moment"] + jnp.square(g)
            new_params.append(p - (lr * s) * g / (jnp.sqrt(m) + epsilon))
            new_states.append({"moment": m})
        return new_params, new_states

    @staticmethod
    def _sparse_update_rule(param, rows, vals, state, scalars, weight_decay=0.0,
                            epsilon=1e-6, lr_scale=1.0):
        # reference adagrad selected-rows kernel: rows-only accumulator
        lr = scalars["lr"] * lr_scale
        m_rows = state["moment"][rows] + jnp.square(vals)
        return (param.at[rows].add(-lr * vals / (jnp.sqrt(m_rows) + epsilon)),
                {"moment": state["moment"].at[rows].set(m_rows)})


class Adadelta(Optimizer):
    _state_names = ["avg_squared_grad", "avg_squared_update"]

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon, self._rho = float(epsilon), float(rho)

    def _static_config(self):
        return super()._static_config() + (("epsilon", self._epsilon),
                                           ("rho", self._rho))

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, epsilon=1e-6,
                     rho=0.95, lr_scales=(), wd_scales=()):
        lr = scalars["lr"]
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            g = _apply_wd(p, g, weight_decay * w)
            asg = rho * st["avg_squared_grad"] + (1 - rho) * jnp.square(g)
            upd = g * jnp.sqrt(st["avg_squared_update"] + epsilon) / jnp.sqrt(asg + epsilon)
            asu = rho * st["avg_squared_update"] + (1 - rho) * jnp.square(upd)
            new_params.append(p - (lr * s) * upd)
            new_states.append({"avg_squared_grad": asg, "avg_squared_update": asu})
        return new_params, new_states


class RMSProp(Optimizer):
    _state_names = ["mean_square", "mean_grad", "momentum_acc"]

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = float(rho), float(epsilon)
        self._momentum, self._centered = float(momentum), bool(centered)

    def _static_config(self):
        return super()._static_config() + (("rho", self._rho),
                                           ("epsilon", self._epsilon),
                                           ("momentum", self._momentum),
                                           ("centered", self._centered))

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, rho=0.95,
                     epsilon=1e-6, momentum=0.0, centered=False, lr_scales=(),
                     wd_scales=()):
        lr = scalars["lr"]
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            g = _apply_wd(p, g, weight_decay * w)
            ms = rho * st["mean_square"] + (1 - rho) * jnp.square(g)
            if centered:
                mg = rho * st["mean_grad"] + (1 - rho) * g
                denom = jnp.sqrt(ms - jnp.square(mg) + epsilon)
            else:
                mg = st["mean_grad"]
                denom = jnp.sqrt(ms + epsilon)
            mom = momentum * st["momentum_acc"] + (lr * s) * g / denom
            new_params.append(p - mom)
            new_states.append({"mean_square": ms, "mean_grad": mg,
                               "momentum_acc": mom})
        return new_params, new_states


class Lamb(Optimizer):
    _state_names = ["moment1", "moment2"]

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = float(beta1), float(beta2), float(epsilon)
        self._exclude_fn = exclude_from_weight_decay_fn

    def _wd_scale(self, p):
        if self._exclude_fn is not None and self._exclude_fn(p):
            return 0.0
        return 1.0

    def _static_config(self):
        return super()._static_config() + (("beta1", self._beta1),
                                           ("beta2", self._beta2),
                                           ("epsilon", self._epsilon))

    @staticmethod
    def _update_rule(params, grads, states, scalars, weight_decay=0.0, beta1=0.9,
                     beta2=0.999, epsilon=1e-6, lr_scales=(), wd_scales=()):
        lr = scalars["lr"]
        t = scalars["step"]
        bc1 = 1.0 - beta1 ** t
        bc2 = 1.0 - beta2 ** t
        new_params, new_states = [], []
        for p, g, st, s, w in zip(params, grads, states, lr_scales, wd_scales):
            m1 = beta1 * st["moment1"] + (1 - beta1) * g
            m2 = beta2 * st["moment2"] + (1 - beta2) * jnp.square(g)
            r = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + epsilon) + (weight_decay * w) * p
            w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
            r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
            trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
            new_params.append(p - (lr * s) * trust * r)
            new_states.append({"moment1": m1, "moment2": m2})
        return new_params, new_states
