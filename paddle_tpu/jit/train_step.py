"""TrainStep — ONE compiled XLA executable for forward + backward + optimizer update.

Reference analog: the static-graph training path (Executor.run over a ProgramDesc that
contains forward, backward and optimizer ops — SURVEY.md §3.3); dygraph users get it
via @to_static around the whole step. This is the peak-performance path on TPU: the
entire step is a single XLA program, so the compiler fuses elementwise chains into the
matmuls, schedules collectives (DP grad psum, TP activation collectives, ZeRO
reshards) and overlaps them with compute — nothing returns to Python between ops.

Works over any current parameter placement: in_shardings are taken from the live
arrays, so the same TrainStep expresses single-chip, DP, TP, and ZeRO runs.

Gradient accumulation (``accumulate_steps=K``) compiles the reference fleet
``gradient_merge`` strategy INTO the step: the executable consumes K stacked
microbatches (every input carries a leading axis of length K), runs the
forward/backward K times via ``jax.lax.scan`` accumulating gradients in fp32
carry buffers, and applies exactly ONE optimizer update per call. Effective
batch grows ×K while parameter and optimizer-state HBM stay flat — the scan
keeps only ONE microbatch's activations live at a time, and the
per-shape-bucket compile count stays 1 regardless of K. ``scan_unroll=K``
unrolls the loop for scheduling freedom at the cost of peak temp memory
(unrolled microbatch temps overlap — measured ~K× temp growth on CPU XLA),
so the default stays a sequential loop.

AMP dynamic loss scaling (``grad_scaler=``) also compiles in: the loss is
scaled before backward, accumulated gradients are unscaled inside the
executable, and a single found-inf flag over ALL K microbatches gates the
update on device (``jnp.where`` keeps params/optimizer state bit-identical on
overflow). The host then replays the eager GradScaler's scale-adjustment
state machine on the flag.
"""
from __future__ import annotations

import itertools
import math
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import monitor as _monitor
from ..monitor import health as _health
from ..monitor import trace as _trace
from ..core import dispatch
from ..core import random as _random
from ..core import remat as _remat
from ..core.tensor import Parameter, Tensor
from ..nn.layer import Layer

__all__ = ["TrainStep"]

# Default scan unroll for the accumulation loop. 1 (a real XLA while loop) is
# the memory-safe choice: the scheduler can only hold ONE microbatch's
# activations live, which is the whole point of accumulating. Unrolling lets
# the scheduler overlap microbatches for speed but measurably inflates peak
# temp memory (observed ~K× on CPU XLA) — opt in via scan_unroll=K only when
# HBM headroom allows.
_DEFAULT_SCAN_UNROLL = 1


class _PlacementDropNeeded(Exception):
    """An adopted array cannot be restored to the compiled placement — the
    AOT executables are stale and must be rebuilt against the new layout."""


def _spec_axes(sharding) -> set:
    """Mesh axis names a NamedSharding actually shards over."""
    if not isinstance(sharding, NamedSharding):
        return set()
    axes = set()
    for s in tuple(sharding.spec):
        if s is None:
            continue
        axes.update(s if isinstance(s, tuple) else (s,))
    return axes


class _ShardedAccumPlan:
    """How the accumulation scan carries ZeRO-2 gradients shard-sized.

    Each entry is either ``("p", j, sharding)`` — param j accumulates on its
    own, the microbatch grad constrained to the shard sharding BEFORE the add
    so the fp32 carry is 1/world_size per device and XLA can overlap the
    microbatch's reduce-scatter with the next microbatch's backward — or
    ``("b", idxs, sizes, pad, flat_sharding)`` — several small grads fused
    into ONE flat fp32 bucket (reference GroupShardedStage2 grad bucketing:
    one reduce-scatter per bucket instead of one tiny collective per param).
    Only grads whose sole sharded axis is "sharding" are bucketed; a grad
    carrying a TP axis keeps its own spec (flattening it would silently
    gather the TP dimension)."""

    def __init__(self, entries, shapes, shardings, world: int):
        self.entries = entries
        self.world = world
        self._shapes = shapes
        self._shardings = shardings

    @property
    def num_buckets(self) -> int:
        return sum(1 for e in self.entries if e[0] == "b")

    def init(self):
        out = []
        for e in self.entries:
            if e[0] == "p":
                _, j, sh = e
                z = jnp.zeros(self._shapes[j], jnp.float32)
                out.append(z if sh is None
                           else jax.lax.with_sharding_constraint(z, sh))
            else:
                _, idxs, sizes, pad, fsh = e
                z = jnp.zeros((sum(sizes) + pad,), jnp.float32)
                out.append(jax.lax.with_sharding_constraint(z, fsh))
        return tuple(out)

    def add(self, acc, grads):
        out = []
        for a, e in zip(acc, self.entries):
            if e[0] == "p":
                _, j, sh = e
                g = grads[j].astype(jnp.float32)
                if sh is not None:
                    g = jax.lax.with_sharding_constraint(g, sh)
                out.append(a + g)
            else:
                _, idxs, sizes, pad, fsh = e
                # constrain each grad at PRODUCTION (the partitioner shards
                # the producing ops — no full-size staging buffer), then fuse
                # the shard-sized pieces into the flat carried bucket
                flat = []
                for j in idxs:
                    g = grads[j].astype(jnp.float32)
                    sh = self._shardings[j]
                    if sh is not None:
                        g = jax.lax.with_sharding_constraint(g, sh)
                    flat.append(g.reshape(-1))
                if pad:
                    flat.append(jnp.zeros((pad,), jnp.float32))
                f = jax.lax.with_sharding_constraint(
                    jnp.concatenate(flat), fsh)
                out.append(a + f)
        return tuple(out)

    def unflatten(self, acc):
        """Per-param fp32 grads out of the carried accumulators; bucket
        members are re-constrained to their per-param shard spec (the
        flat→dim reshard the optimizer states are laid out for)."""
        grads = [None] * len(self._shapes)
        for a, e in zip(acc, self.entries):
            if e[0] == "p":
                grads[e[1]] = a
            else:
                _, idxs, sizes, pad, fsh = e
                off = 0
                for j, n in zip(idxs, sizes):
                    g = a[off:off + n].reshape(self._shapes[j])
                    sh = self._shardings[j]
                    if sh is not None:
                        g = jax.lax.with_sharding_constraint(g, sh)
                    grads[j] = g
                    off += n
        return tuple(grads)

    def accum_bytes(self) -> int:
        """Per-device fp32 accumulator residency inside the executable."""
        total = 0
        for e in self.entries:
            if e[0] == "p":
                _, j, sh = e
                total += 4 * _shard_elems(self._shapes[j], sh)
            else:
                _, idxs, sizes, pad, _ = e
                total += 4 * (sum(sizes) + pad) // self.world
        return total

    def ideal_bytes(self) -> int:
        """The sharding CONTRACT's per-device floor: every grad whose spec
        shards over the mesh carries shard-sized, unshardable grads (no
        divisible dim) legitimately full-size. Computed from the shardings,
        not the plan's entries — a planner regression that drops a
        constraint raises accum_bytes above this without moving it."""
        return sum(4 * _shard_elems(shape, sh)
                   for shape, sh in zip(self._shapes, self._shardings))


def _shard_elems(shape, sh) -> int:
    """Per-device element count of an array at sharding ``sh`` — true
    shard-SHAPE math (ceil per sharded dim), not ceil of the flattened size,
    which under-counts when a sharded dim doesn't divide evenly."""
    if not isinstance(sh, NamedSharding):
        return int(math.prod(shape) if shape else 1)
    spec = tuple(sh.spec)
    elems = 1
    for i, dim in enumerate(shape):
        s = spec[i] if i < len(spec) else None
        if s is None:
            elems *= dim
            continue
        axes = s if isinstance(s, tuple) else (s,)
        d = 1
        for a in axes:
            d *= sh.mesh.shape.get(a, 1)
        elems *= -(-dim // d)
    return int(elems)


def _plan_sharded_accum(shapes, shardings, bucket_bytes: int):
    """Greedy in-order bucketing of shard-able grads for the scan carry;
    anything ineligible (no "sharding" axis in its spec, a TP axis present,
    or larger than the bucket cap) accumulates per-param."""
    world = 1
    mesh = None
    for sh in shardings:
        if isinstance(sh, NamedSharding):
            mesh = sh.mesh
            world = mesh.shape.get("sharding", 1)
            break
    entries, cur, cur_sizes, cur_bytes = [], [], [], 0

    def flush():
        nonlocal cur, cur_sizes, cur_bytes
        if len(cur) == 1:
            # a lone bucket member gains nothing from the flat round-trip
            entries.append(("p", cur[0], shardings[cur[0]]))
        elif cur:
            tot = sum(cur_sizes)
            pad = (-tot) % world
            fsh = NamedSharding(mesh, PartitionSpec("sharding"))
            entries.append(("b", tuple(cur), tuple(cur_sizes), pad, fsh))
        cur, cur_sizes, cur_bytes = [], [], 0

    for j, (shape, sh) in enumerate(zip(shapes, shardings)):
        n = int(math.prod(shape) if shape else 1)
        nbytes = 4 * n
        bucketable = (bucket_bytes > 0 and nbytes <= bucket_bytes
                      and _spec_axes(sh) == {"sharding"})
        if not bucketable:
            flush()
            entries.append(("p", j, sh))
            continue
        if cur and cur_bytes + nbytes > bucket_bytes:
            flush()
        cur.append(j)
        cur_sizes.append(n)
        cur_bytes += nbytes
    flush()
    return _ShardedAccumPlan(entries, shapes, shardings, world)


class TrainStep:
    """Compile (model fwd → loss → grads → optimizer update) into one executable.

    loss_fn(outputs, *labels) -> scalar Tensor; if None, the model must return the
    loss itself (paddle GPTForCausalLM-style `model(ids, labels=...)` works by
    passing labels through inputs).

    accumulate_steps=K (K>1): every input must be K stacked microbatches
    (leading axis K, e.g. via ``io.DeviceLoader(stack_batches=K)``); one call
    runs K fwd/bwd passes and ONE optimizer update on the accumulated
    gradients. ``average_grads=True`` (default) divides the accumulated sum
    by K — the fleet ``gradient_merge_configs["avg"]`` semantics; False keeps
    the raw sum, matching an eager loop of ``loss.backward()`` calls.
    Wrapping the optimizer in ``fleet.GradientMergeOptimizer`` (or enabling
    the ``gradient_merge`` strategy) sets both automatically.

    grad_scaler: an ``amp.GradScaler`` whose dynamic loss scaling should be
    compiled into the step (found-inf detection across all microbatches,
    on-device skip-update, host-side scale adjustment).
    """

    # per-instance id for the goodput FLOP ledger: two TrainSteps sharing
    # one monitor session (hapi's + a hand-built one, a GAN-style pair)
    # must never bill each other's dispatches — the DecodeEngine keys per
    # engine_id for the same reason
    _ids = itertools.count()

    def __init__(self, model: Layer, optimizer, loss_fn: Optional[Callable] = None,
                 donate_params: bool = True, fast_path: bool = True,
                 accumulate_steps: Optional[int] = None,
                 average_grads: Optional[bool] = None,
                 grad_scaler=None, scan_unroll: int = _DEFAULT_SCAN_UNROLL,
                 grad_bucket_bytes: Optional[int] = None):
        # unwrap distributed facades down to the real Layer
        self._model = model
        while hasattr(self._model, "_layers"):
            self._model = self._model._layers
        self._opt = optimizer
        # ZeRO>=2 wrappers declare how grads must come out of backward; capture
        # before unwrapping so the constraint compiles into the step
        self._grad_spec_fn = getattr(optimizer, "_grad_spec", None)
        # collective coalescing for the in-scan reduce-scatters: grads smaller
        # than this fuse into flat buckets (None adopts the ZeRO wrapper's
        # _grad_bucket_bytes — set via group_sharded_parallel /
        # sharding_configs, itself defaulting to off; 0 = one collective
        # per param)
        if grad_bucket_bytes is None:
            grad_bucket_bytes = getattr(optimizer, "_grad_bucket_bytes", None)
        self._grad_bucket_bytes = int(grad_bucket_bytes or 0)
        self._accum_plan = None
        # fleet.GradientMergeOptimizer is a thin adapter onto the compiled
        # accumulation machinery: adopt its k_steps/avg while unwrapping
        while hasattr(self._opt, "_inner_opt"):
            if getattr(self._opt, "_gradient_merge", False):
                if accumulate_steps is None:
                    accumulate_steps = self._opt.k_steps
                if average_grads is None:
                    average_grads = self._opt.avg
            self._opt = self._opt._inner_opt
        self._acc_steps = max(int(accumulate_steps or 1), 1)
        self._avg = True if average_grads is None else bool(average_grads)
        self._scan_unroll = max(int(scan_unroll), 1)
        self._scaler = grad_scaler
        self._scaler_on = grad_scaler is not None and grad_scaler.is_enable()
        self._loss_fn = loss_fn
        self._donate = donate_params
        named = list(self._model.named_parameters())
        self._params: List[Parameter] = [p for _, p in named]
        # leaf names in param order: the health plane's trip attribution and
        # the PADDLE_HEALTH_FAULT seam both address leaves by name
        self._param_names: List[str] = [n for n, _ in named]
        # trainable param count for the goodput plane's analytic 6ND FLOP
        # model (fallback + cross-check next to cost_analysis at each mint)
        self._n_train_params = sum(
            int(math.prod(p.shape)) if p.ndim else 1
            for p in self._params if p.trainable)
        self._buffers = [b for _, b in self._model.named_buffers()]
        self._buffers.append(_random.rng_state_tensor())
        self._compiled = None
        # fast path: AOT executables keyed by input signature + a reusable
        # flat argument state (see _fast_dispatch); _fast_bucket numbers
        # the executables (by id) in the order they were minted
        self._fast_path = fast_path
        self._fast = {}
        self._fast_bucket = {}
        self._fast_state = None
        self._fast_meta = None
        # recompile-sentinel state: the previous step's input signature, so a
        # recompile event can name exactly which leaves diverged (only
        # maintained while the monitor is enabled — zero stores otherwise);
        # _mon_sig_bucket maps slow-path signatures to their mint count so
        # steady-state jit dispatches FLOP-attribute to the RIGHT bucket
        self._mon_prev_sig = None
        self._mon_sig_bucket = {}
        self._gp_id = next(TrainStep._ids)
        # span state: the trace of the call in flight (monitor/trace.py) and
        # the step counter that is its trace id
        self._cur_trace = None
        self._trace_n = 0
        # health-plane state: the CompiledHealth spec captured at build time
        # (None when the monitor is off or PADDLE_HEALTH=0 — the program is
        # then byte-for-byte what it always was) and the step counter the
        # host sampling cadence keys on
        self._health_spec = None
        self._health_n = 0
        self._opt._ensure_all_states()
        # ZeRO / hybrid optimizers place their states on construction paths that
        # run inside step(); trigger placement explicitly when present
        placer = getattr(optimizer, "_place_states", None)
        if placer is not None:
            placer()
        # the wrapper (not the unwrapped inner opt): shard-residency gauges
        # and output-placement pinning key off it
        self._zero_opt = optimizer if placer is not None else None
        # commit every array to its current placement: uncommitted inputs vs
        # committed first-step outputs would otherwise trigger a second compile.
        # Multi-host arrays are already committed (and bare device_put on a
        # non-addressable array is an error) — leave them be.
        def commit(a):
            if getattr(a, "is_fully_addressable", True):
                return jax.device_put(a)
            return a

        # ZeRO working params live mesh-REPLICATED between steps (stage-2's
        # update-then-all-gather): commit params that predate the mesh onto
        # it up front so _build pins param outputs to the replicated
        # placement. Left single-device, XLA's propagation would hand back
        # shard-laid params — a stealth ZeRO-3 where every forward re-gathers
        # every microbatch. Params already carrying a NamedSharding (TP,
        # stage-3) keep their layout.
        replicate = None
        if self._zero_opt is not None:
            from ..distributed.env import get_mesh
            mesh = get_mesh()
            if mesh is not None and mesh.shape.get("sharding", 1) > 1:
                replicate = NamedSharding(mesh, PartitionSpec())

        for p in self._params:
            if (replicate is not None
                    and not isinstance(getattr(p._data, "sharding", None),
                                       NamedSharding)
                    and getattr(p._data, "is_fully_addressable", True)):
                p._data = jax.device_put(p._data, replicate)
            else:
                p._data = commit(p._data)
        for b in self._buffers:
            b._data = commit(b._data)
        for st in self._opt._accumulators.values():
            for k in st:
                st[k] = commit(st[k])
        for k in list(self._opt._master_weights):
            self._opt._master_weights[k] = commit(
                self._opt._master_weights[k])

    # ------------------------------------------------------------------ build

    def _build(self, example_inputs):
        params = self._params
        buffers = self._buffers
        model = self._model
        loss_fn = self._loss_fn
        opt = self._opt
        opt_cls = type(opt)
        n_p, n_b = len(params), len(buffers)

        trainables = [p.trainable for p in params]
        # health plane: captured at build time so its stat block compiles
        # INTO this executable's outputs (flags are data, not shape — one
        # program per bucket with health on or off, never both)
        mon0 = _monitor._active
        health = None
        if mon0 is not None and mon0.health.enabled:
            diff_names = [n for n, p in zip(self._param_names, params)
                          if p.trainable]
            health = mon0.health.compiled_spec(diff_names)
        self._health_spec = health
        static = dict(opt._static_config())
        static["lr_scales"] = tuple(
            float(p.optimize_attr.get("learning_rate", 1.0))
            for p in params if p.trainable)
        # AdamW apply_decay_param_fun / Lamb exclusion compiled into the step
        static["wd_scales"] = tuple(
            opt._wd_scale(p) for p in params if p.trainable)
        # grad clip (e.g. ClipGradByGlobalNorm) is pure jnp math — compile it in,
        # matching eager Optimizer.step (reference static path compiles clip ops)
        grad_clip = opt._grad_clip
        # ZeRO stage-2: force each grad sharded at production (reduce-scatter
        # fused into the backward) rather than replicated-then-resharded
        grad_shardings = None
        if self._grad_spec_fn is not None:
            grad_shardings = [self._grad_spec_fn(p) for p in params
                              if p.trainable]

        # ZeRO output-placement pins: the update runs on shard-sized
        # masters/states, so XLA's propagation would hand back shard-laid
        # params; constrain each output to its INPUT placement instead —
        # masters/moments stay shard-sized, the bf16/working params are
        # all-gathered inside the same executable (ZeRO's update-then-
        # all-gather), and the fast path's outputs-feed-inputs contract
        # keeps holding
        def _mesh_sh(arr):
            sh = getattr(arr, "sharding", None)
            return sh if isinstance(sh, NamedSharding) else None

        zero_out = self._zero_opt is not None
        if zero_out:
            param_keep = [_mesh_sh(p.value()) for p in params]
            master_keep = [_mesh_sh(opt._master_weights[id(p)])
                           if id(p) in opt._master_weights else None
                           for p in params]
            state_keep = [{name: _mesh_sh(opt._accumulators[id(p)][name])
                           for name in opt._state_names}
                          if p.trainable and id(p) in opt._accumulators
                          else {} for p in params]
        else:
            param_keep = [None] * n_p
            master_keep = [None] * n_p
            state_keep = [{}] * n_p

        def keep(x, sh):
            return x if sh is None else \
                jax.lax.with_sharding_constraint(x, sh)

        # the mesh this program is laid over, read off its own arrays (ZeRO/
        # TP-placed params, a DeviceLoader-sharded batch) — never assumed
        # from a global mesh some earlier job may have left behind
        mesh = next((a.sharding.mesh
                     for a in [p.value() for p in params]
                     + list(example_inputs)
                     if isinstance(getattr(a, "sharding", None),
                                   NamedSharding)
                     and a.sharding.mesh.size > 1), None)

        def run_model(param_arrays, buffer_arrays, input_arrays):
            ctx = dispatch.TraceContext(mesh=mesh)
            saved_p = [p._data for p in params]
            saved_b = [b._data for b in buffers]
            dispatch.push_trace(ctx)
            # health activation taps: core/remat.tag_array records (sumsq,
            # count) for each named activation while this collector is open
            # (suspended inside scan bodies / jax.checkpoint regions, whose
            # inner tracers cannot escape to the step's outputs)
            tap_cm = _health.collect_taps() if health is not None else None
            taps = tap_cm.__enter__() if tap_cm is not None else None
            try:
                for p, a in zip(params, param_arrays):
                    p._data = a
                for b, a in zip(buffers, buffer_arrays):
                    b._data = a
                tensors = [Tensor(a) for a in input_arrays]
                out = model(*tensors)
                if loss_fn is not None:
                    loss = loss_fn(out)
                elif isinstance(out, Tensor):
                    loss = out
                else:
                    loss = out[-1]  # (logits, loss) convention
                updates = {id(t): arr for t, arr in ctx.buffer_updates}
                new_buffers = tuple(updates.get(id(b), arr)
                                    for b, arr in zip(buffers, buffer_arrays))
                act = taps.harvest() if taps is not None else {}
                return loss.value(), new_buffers, act
            finally:
                if tap_cm is not None:
                    tap_cm.__exit__(None, None, None)
                dispatch.pop_trace()
                ctx.restore()
                for p, d in zip(params, saved_p):
                    p._data = d
                for b, d in zip(buffers, saved_b):
                    b._data = d

        # AMP-O2: per-param master-weight flag (fp32 copy lives in the optimizer,
        # bf16/fp16 working copy in the model — reference multi_precision path)
        use_master = [p.trainable and id(p) in opt._master_weights for p in params]

        acc_on = self._acc_steps > 1
        scaler_on = self._scaler_on
        avg = self._avg

        # ZeRO-2 + accumulation: the reduce-scatter moves INTO the scan body
        # (each microbatch's grads constrained to the shard sharding before
        # the add), so the fp32 accumulators carry 1/world_size per device
        # and the collective overlaps the next microbatch's backward
        accum_plan = None
        if acc_on and grad_shardings is not None and any(
                sh is not None for sh in grad_shardings):
            diff_shapes = [tuple(p.shape) for p in params if p.trainable]
            accum_plan = _plan_sharded_accum(diff_shapes, grad_shardings,
                                             self._grad_bucket_bytes)
        self._accum_plan = accum_plan

        def repack(param_arrays, masters, states, new_upd, new_states_diff):
            """Merge updated trainables back into the full pytrees, pinning
            ZeRO outputs to their input placements (see keep above)."""
            new_params, new_masters, new_states = [], [], []
            ui, si = iter(new_upd), iter(new_states_diff)
            for i, (a, m, s, t, um) in enumerate(
                    zip(param_arrays, masters, states, trainables,
                        use_master)):
                if not t:
                    new_params.append(a)
                    new_masters.append(m)
                    new_states.append(s)
                    continue
                u = next(ui)
                ns = next(si)
                if zero_out:
                    ns = {name: keep(v, state_keep[i].get(name))
                          for name, v in ns.items()}
                new_states.append(ns)
                if um:
                    new_masters.append(keep(u, master_keep[i]))
                    new_params.append(keep(u.astype(a.dtype), param_keep[i]))
                else:
                    new_masters.append(m)
                    new_params.append(keep(u, param_keep[i]))
            return tuple(new_params), tuple(new_masters), tuple(new_states)

        def microbatch_grads(param_arrays, buffer_arrays, input_arrays,
                             scalars):
            """One fwd/bwd over a single microbatch. With a scaler, the
            differentiated quantity is the SCALED loss (reference
            scaler.scale(loss).backward()); the reported loss stays raw."""
            def loss_of(diff_params):
                full = []
                di = iter(diff_params)
                for a, t in zip(param_arrays, trainables):
                    full.append(next(di) if t else a)
                loss, new_buffers, act = run_model(tuple(full), buffer_arrays,
                                                   input_arrays)
                if scaler_on:
                    return (loss * scalars["loss_scale"].astype(loss.dtype),
                            (loss, new_buffers, act))
                return loss, (loss, new_buffers, act)

            diff_in = tuple(a for a, t in zip(param_arrays, trainables) if t)
            (_, (loss, new_buffers, act)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff_in)
            return loss, new_buffers, act, grads

        def step_fn_accum(param_arrays, masters, states, buffer_arrays,
                          scalars, input_arrays):
            diff_in = tuple(a for a, t in zip(param_arrays, trainables) if t)
            if acc_on:
                # K from the traced shape: a different microbatch count is
                # just another shape bucket, not a different TrainStep
                k = int(input_arrays[0].shape[0])
                if accum_plan is not None:
                    acc0 = accum_plan.init()
                else:
                    acc0 = tuple(jnp.zeros(a.shape, jnp.float32)
                                 for a in diff_in)

                def body(carry, mb_inputs):
                    bufs, acc = carry
                    loss, new_bufs, act_mb, g = microbatch_grads(
                        param_arrays, bufs, mb_inputs, scalars)
                    if accum_plan is not None:
                        acc = accum_plan.add(acc, g)
                    else:
                        acc = tuple(a + gi.astype(jnp.float32)
                                    for a, gi in zip(acc, g))
                    # activation stats ride the scan's ys (stacked [K],
                    # averaged below) — they escape the body legitimately,
                    # unlike values tapped INSIDE an inner scan/remat trace
                    return (new_bufs, acc), (loss, act_mb)

                (new_buffers, grads), (losses, acts) = jax.lax.scan(
                    body, (tuple(buffer_arrays), acc0), input_arrays,
                    unroll=min(self._scan_unroll, k))
                if accum_plan is not None:
                    grads = accum_plan.unflatten(grads)
                loss = jnp.mean(losses)
                act = {n: jnp.mean(v) for n, v in acts.items()}
                factor = (1.0 / k) if avg else 1.0
            else:
                k = 1
                loss, new_buffers, act, grads = microbatch_grads(
                    param_arrays, buffer_arrays, input_arrays, scalars)
                factor = 1.0

            found_inf = None
            if scaler_on:
                # unscale once over the accumulated sum (1/scale · 1/K fused
                # into one multiply); a non-finite value produced by ANY of
                # the K microbatches survives summation, so one flag over the
                # accumulated grads covers the whole window
                scale_f = factor / scalars["loss_scale"]
                grads = tuple(g * scale_f.astype(g.dtype) for g in grads)
                # under ZeRO-2 each grad is already shard-sized here, so the
                # finite-reduction is a per-shard partial + tiny all-reduce
                from ..amp.grad_scaler import GradScaler as _GS
                found_inf = _GS._found_inf_of(grads)
            elif factor != 1.0:
                grads = tuple(g * jnp.asarray(factor, g.dtype) for g in grads)

            if grad_shardings is not None and accum_plan is None:
                grads = tuple(
                    g if sh is None else jax.lax.with_sharding_constraint(g, sh)
                    for g, sh in zip(grads, grad_shardings))
            # health stats read the UNCLIPPED grads: a NaN global norm would
            # smear the clip's NaN across every group and destroy attribution
            health_grads = tuple(grads) if health is not None else None
            if grad_clip is not None:
                grads = [g for _, g in grad_clip(list(zip(diff_in, grads)))]

            upd_in = [m if um else a
                      for a, m, um, t in zip(param_arrays, masters, use_master,
                                             trainables) if t]
            diff_states = [s for s, t in zip(states, trainables) if t]
            with jax.named_scope("optimizer"):
                new_upd, new_states_diff = opt_cls._update_rule(
                    upd_in,
                    [g.astype(u.dtype) for g, u in zip(grads, upd_in)],
                    diff_states, scalars, **static)
            if scaler_on:
                # overflow anywhere in the window: the whole K-step update is
                # discarded on device (params/state bit-identical), exactly
                # the eager scaler.step() skip
                new_upd = [jnp.where(found_inf, u, nu)
                           for u, nu in zip(upd_in, new_upd)]
                new_states_diff = [
                    {name: jnp.where(found_inf, s[name], ns[name])
                     for name in ns}
                    for s, ns in zip(diff_states, new_states_diff)]
            new_params, new_masters, new_states = repack(
                param_arrays, masters, states, new_upd, new_states_diff)
            loss_out = ({"loss": loss, "found_inf": found_inf} if scaler_on
                        else loss)
            if health is not None:
                # on a skipped update new_upd was where()'d back to upd_in,
                # so the param digest correctly reports "weights unchanged"
                h = health.pack(loss, health_grads, new_upd, upd_in, act)
                loss_out = dict(loss_out) if scaler_on \
                    else {"loss": loss}
                loss_out["health"] = h
            return (loss_out, new_params, new_masters, new_states,
                    tuple(new_buffers))

        def step_fn(param_arrays, masters, states, buffer_arrays, scalars,
                    input_arrays):
            def loss_of(diff_params):
                full = []
                di = iter(diff_params)
                for a, t in zip(param_arrays, trainables):
                    full.append(next(di) if t else a)
                loss, new_buffers, act = run_model(tuple(full), buffer_arrays,
                                                   input_arrays)
                return loss, (new_buffers, act)

            diff_in = tuple(a for a, t in zip(param_arrays, trainables) if t)
            (loss, (new_buffers, act)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff_in)

            if grad_shardings is not None:
                grads = tuple(
                    g if sh is None else jax.lax.with_sharding_constraint(g, sh)
                    for g, sh in zip(grads, grad_shardings))

            # health stats read the UNCLIPPED grads (attribution — see above)
            health_grads = tuple(grads) if health is not None else None
            if grad_clip is not None:
                grads = [g for _, g in grad_clip(list(zip(diff_in, grads)))]

            # the update runs on the master copy where one exists (fp32 math),
            # else directly on the param
            upd_in = [m if um else a
                      for a, m, um, t in zip(param_arrays, masters, use_master,
                                             trainables) if t]
            diff_states = [s for s, t in zip(states, trainables) if t]
            with jax.named_scope("optimizer"):
                new_upd, new_states_diff = opt_cls._update_rule(
                    upd_in,
                    [g.astype(u.dtype) for g, u in zip(grads, upd_in)],
                    diff_states, scalars, **static)
            new_params, new_masters, new_states = repack(
                param_arrays, masters, states, new_upd, new_states_diff)
            loss_out = loss
            if health is not None:
                loss_out = {"loss": loss,
                            "health": health.pack(loss, health_grads,
                                                  new_upd, upd_in, act)}
            return (loss_out, new_params, new_masters, new_states,
                    new_buffers)

        # donate params too: __call__ re-reads p.value() fresh each step and
        # immediately replaces p._data with the step's output, so the input
        # buffers are dead after dispatch — donating them lets XLA alias
        # new_params onto them (saves a params-sized allocation + copy)
        donate = (0, 1, 2, 3) if self._donate else ()
        # the plain path stays byte-for-byte the program it always was;
        # accumulation/scaler compile through the extended step function
        fn = step_fn_accum if (acc_on or scaler_on) else step_fn
        self._compiled = jax.jit(fn, donate_argnums=donate)

    @property
    def num_compiles(self) -> int:
        """Distinct executables compiled so far (one per input-shape bucket).

        The bucketing contract (io/bucketing.py) promises a workload compiles
        at most len(boundaries) of them; this is the observable that tests and
        capacity planning assert against."""
        if self._fast:
            return len(self._fast)
        if self._compiled is None:
            return 0
        return self._compiled._cache_size()

    # ------------------------------------------------------------------ call

    def __call__(self, *inputs):
        mon = _monitor._active
        if mon is not None and mon.health.fault is not None:
            # chaos seam: a scheduled PADDLE_HEALTH_FAULT poisons a live
            # param host-side (same sharding, so the fast path re-adopts it
            # without a recompile) before this call dispatches
            mon.health.fault.maybe_fire(
                list(zip(self._param_names, self._params)), emit=mon.emit)
        # one trace per call (trace id = step number; head-sampled where a
        # sink is on); spans the loader recorded since the previous step
        # (wait/fetch/H2D, checkpoint saves) are adopted as children, so
        # the waterfall shows what the step waited on before it dispatched
        self._trace_n += 1
        with _trace.start_trace("train_step", self._trace_n, "step",
                                step=self._trace_n) as t:
            # what a stall record of this step's value fetch
            # (``tensor/sync``) takes its deltas from
            _trace.host_clocks()
            self._cur_trace = t
            try:
                return self._call_impl(inputs)
            except BaseException as e:
                # flight-recorder post-mortem: dump the recent-event ring
                # before the exception unwinds out of the training loop
                t.event("crash", exc=type(e).__name__)
                t.escalate("crash")
                _monitor.on_crash(e)
                raise
            finally:
                self._cur_trace = None

    def _call_impl(self, inputs):
        mon = _monitor._active
        with _trace.span("train_step/prepare") as prep:
            input_arrays = tuple(
                t.value() if isinstance(t, Tensor) else jnp.asarray(t)
                for t in inputs)
            if self._acc_steps > 1:
                # the scan takes K from the traced shape — an unstacked
                # batch would silently run shape[0] SINGLE-SAMPLE
                # microbatches (wrong batch semantics, K× the intended
                # update count), so enforce the stacking contract loudly
                for i, a in enumerate(input_arrays):
                    if getattr(a, "ndim", 0) == 0 \
                            or a.shape[0] != self._acc_steps:
                        raise ValueError(
                            f"TrainStep(accumulate_steps={self._acc_steps}) "
                            f"expects every input stacked with leading axis "
                            f"{self._acc_steps} (K microbatches per call); "
                            f"input[{i}] has shape "
                            f"{tuple(getattr(a, 'shape', ()))} — stack with "
                            f"io.stack_microbatches or "
                            f"DeviceLoader(stack_batches={self._acc_steps})")
            if self._fast_path:
                exe, scalars, sig = self._fast_prepare(input_arrays)
            else:
                if self._compiled is None:
                    self._build(input_arrays)
                # jit trace-cache size before the call: a growth across the
                # call IS a recompile (the slow path compiles lazily inside
                # __call__)
                n0 = self._compiled._cache_size() if mon is not None else 0
                param_arrays, masters, states, buffer_arrays, scalars = \
                    self._gather_args()
                if mon is not None:
                    _remat.reset_trace_stats()  # a cache miss traces inside
        if self._fast_path:
            # the step's entry instant books the pre-dispatch host work
            # (state refresh, scalars, arg handling) as goodput overhead
            return self._fast_dispatch(exe, scalars, sig, input_arrays,
                                       prep.t0)
        with _trace.span("train_step/dispatch", path="jit",
                         microbatches=self._microbatches(input_arrays)) as d:
            loss_out, new_params, new_masters, new_states, new_buffers = \
                self._compiled(param_arrays, masters, states, buffer_arrays,
                               scalars, input_arrays)

        if mon is not None:
            sig = self._input_sig(input_arrays)
            n1 = self._compiled._cache_size()
            if n1 > n0:
                # the dispatch above WAS a compile; link the sentinel
                self._cur_trace.event("recompile", count=n1, path="jit")
                # the jit path compiles INSIDE the dispatch call — no
                # separate compile wall exists, so the dispatch span itself
                # classifies as compile time in the goodput ledger
                self._mon_sig_bucket[sig] = n1
                mon.train_step_compiled(
                    sig, self._mon_prev_sig, compile_s=None, count=n1,
                    path="jit", span=(d.t0, d.t1), **self._flop_kwargs(
                        input_arrays))
                if self._acc_steps > 1:
                    mon.accum_config(self._acc_steps, self._grad_acc_bytes())
                self._emit_shard_gauges(mon)
                self._emit_remat_gauges(mon)
            else:
                # steady-state dispatch latency; a cache-miss call is compile
                # time, not dispatch, and is already covered by the recompile
                # event
                mon.step_event(d.dur_s,
                               microbatches=self._microbatches(input_arrays),
                               bucket=self._mon_sig_bucket.get(sig),
                               span=(d.t0, d.t1), step_id=self._gp_id)
            self._mon_prev_sig = sig

        opt = self._opt
        with dispatch.no_grad():
            for p, a, m, s in zip(self._params, new_params, new_masters,
                                  new_states):
                p._data = a
                if p.trainable:
                    opt._accumulators[id(p)] = dict(s)
                if id(p) in opt._master_weights:
                    opt._master_weights[id(p)] = m
            for b, a in zip(self._buffers, new_buffers):
                b._data = a
        return Tensor(self._finish_loss(loss_out))

    def _gather_args(self):
        """Rebuild the full argument pytrees from the live framework objects
        (the slow path does this every step; the fast path only on (re)entry)."""
        opt = self._opt
        params = self._params
        for p in params:
            if p.trainable:
                opt._ensure_state(p)
        param_arrays = tuple(p.value() for p in params)
        masters = tuple(opt._master_weights.get(id(p), ()) for p in params)
        states = tuple(
            {name: opt._accumulators[id(p)][name] for name in opt._state_names}
            if p.trainable else {} for p in params)
        buffer_arrays = tuple(b.value() for b in self._buffers)
        scalars = self._step_scalars()
        return param_arrays, masters, states, buffer_arrays, scalars

    def _step_scalars(self):
        """The per-step device scalars: the optimizer's lr/step, plus the
        current loss scale when a GradScaler is compiled in (a device input,
        so dynamic scale changes never recompile)."""
        scalars = self._opt._scalars(self._opt.get_lr())
        if self._scaler_on:
            from ..core.lazy import scalar_const
            scalars = dict(scalars)
            scalars["loss_scale"] = scalar_const(
                float(self._scaler._scale)).astype(jnp.float32)
        return scalars

    def _flop_kwargs(self, input_arrays) -> dict:
        """Per-mint FLOP-ledger context: tokens one call consumes (every
        element of the first input — [B, S] ids, [K, B, S] stacked), the
        analytic 6ND model over the trainable params, and whether the trace
        rematerializes (measured FLOPs then include recompute replays, so
        MFU must source from the analytic model while HFU stays measured).
        For a transformer whose config exposes num_layers/hidden_size, the
        attention-dot term (12·L·d·S per token, fwd+bwd) is added: without
        it the ledger's analytic would sit ~10% under the measured count
        on the GPT config, and under recompute — where the analytic is
        the sole MFU source — MFU would read low by pure constant skew.
        """
        from ..monitor.goodput import analytic_train_flops_per_token
        tokens = 1
        seq = 0
        if input_arrays and getattr(input_arrays[0], "ndim", 0):
            shape = input_arrays[0].shape
            tokens = int(math.prod(shape))
            if len(shape) >= 2:
                seq = int(shape[-1])
        cfg = getattr(self._model, "config", None)
        fpt = analytic_train_flops_per_token(
            self._n_train_params, getattr(cfg, "num_layers", None),
            getattr(cfg, "hidden_size", None), seq or None)
        # SPMD span: cost_analysis reports the PER-DEVICE module, so the
        # global analytic must divide by the device count for the
        # cross-check (and the MFU ratios) to stay per-chip figures
        devices = 1
        for p in self._params:
            try:
                devices = max(devices, len(p._data.sharding.device_set))
            except Exception:
                pass
        return dict(tokens=tokens, analytic_flops=fpt * tokens,
                    devices=devices, step_id=self._gp_id,
                    recompute=bool(getattr(self._model, "_recompute_wanted",
                                           False)))

    def _microbatches(self, input_arrays) -> int:
        if self._acc_steps > 1 and input_arrays \
                and getattr(input_arrays[0], "ndim", 0) > 0:
            return int(input_arrays[0].shape[0])
        return 1

    def _grad_acc_bytes(self) -> int:
        """Per-device HBM held by the fp32 gradient accumulators inside the
        executable — shard-sized (1/world_size) under ZeRO-2 in-scan
        reduce-scatter, full-size otherwise."""
        if self._accum_plan is not None:
            return self._accum_plan.accum_bytes()
        return self._full_grad_bytes()

    def _full_grad_bytes(self) -> int:
        return sum(4 * int(math.prod(p.shape) if p.ndim else 1)
                   for p in self._params if p.trainable)

    def _emit_shard_gauges(self, mon):
        """shard/* gauges: what is shard-sized right now vs the 1/world ideal
        (tools/metrics_summary.py flags accum_bytes drifting above ideal as a
        lost-constraint regression)."""
        if self._zero_opt is None:
            return
        from ..distributed.env import get_mesh
        mesh = get_mesh()
        world = mesh.shape.get("sharding", 1) if mesh is not None else 1
        if world <= 1:
            return
        plan = self._accum_plan
        state_bytes_fn = getattr(self._zero_opt, "_shard_state_bytes", None)
        # the ideal is only a contract for stage >= 2 (an in-scan plan
        # exists): stage-1 "os" accumulators are LEGITIMATELY full-size —
        # emitting an ideal there would make metrics_summary's
        # lost-constraint WARNING fire on a healthy, documented config. The
        # plan's ideal also keeps unshardable params (no divisible dim) out
        # of the comparison: they are full-size by design, not regression.
        mon.shard_config(
            world=world,
            accum_bytes=self._grad_acc_bytes() if self._acc_steps > 1 else 0,
            accum_ideal_bytes=(plan.ideal_bytes()
                               if self._acc_steps > 1 and plan is not None
                               else 0),
            opt_state_bytes=(state_bytes_fn() if state_bytes_fn is not None
                             else 0),
            buckets=plan.num_buckets if plan is not None else 0)

    def _emit_remat_gauges(self, mon, compiled=None, baseline_args=None):
        """remat/* gauges: what the trace actually checkpointed vs what the
        model declared. ``remat/requested`` with ``remat/regions == 0`` is
        the lost-checkpoint signature (recompute configured but nothing
        routed through fleet.recompute / the scan remat) —
        tools/metrics_summary.py WARNs on it, like the ZeRO lost-constraint
        check. With env ``PADDLE_REMAT_BASELINE=1`` a no-remat twin of the
        executable is also compiled (one extra compile per bucket) so the
        gauges carry the MEASURED saved-residual bytes from
        ``compiled.memory_analysis()``, not an estimate. The twin only
        exists on the AOT path (callers pass ``compiled``/``baseline_args``
        from _build_fast), where per-step dispatch never touches the jit
        trace cache — so the clear_cache bracketing below cannot cost the
        slow path a recompile."""
        import os
        wanted = bool(getattr(self._model, "_recompute_wanted", False))
        stats = _remat.trace_stats()
        if not wanted and stats["regions"] == 0:
            return
        base_total = saved = None
        if (compiled is not None and baseline_args is not None
                and os.environ.get("PADDLE_REMAT_BASELINE")
                and hasattr(self._model, "enable_recompute")):
            from ..monitor.memory import executable_memory_stats
            cfg = getattr(self._model, "config", None)
            gran = getattr(cfg, "recompute_granularity", None)
            interval = getattr(cfg, "recompute_interval", 1)
            if gran and gran != "none":
                base = None
                try:
                    self._model.enable_recompute("none")
                    args, input_arrays = baseline_args
                    # the jit trace cache keys on avals only — without the
                    # clear, lower() would reuse the WITH-remat jaxpr and
                    # the "baseline" would measure the same executable
                    self._compiled.clear_cache()
                    base = self._compiled.lower(*args, input_arrays).compile()
                except Exception as e:
                    # diagnostics-only: a twin that fails to compile must
                    # never take down the training step it was measuring
                    import warnings
                    warnings.warn(f"PADDLE_REMAT_BASELINE twin compile "
                                  f"failed ({type(e).__name__}: {e}); "
                                  f"emitting remat gauges without the "
                                  f"measured baseline", RuntimeWarning)
                finally:
                    self._model.enable_recompute(gran, interval)
                    self._compiled.clear_cache()
                bs = executable_memory_stats(base) if base is not None \
                    else None
                ws = executable_memory_stats(compiled)
                if bs is not None and ws is not None:
                    base_total = bs["total_bytes"]
                    saved = bs["total_bytes"] - ws["total_bytes"]
        mon.remat_compiled(wanted, stats["regions"], stats["policy"],
                           stats["total_named_bytes"], stats["named_bytes"],
                           baseline_total_bytes=base_total,
                           saved_residual_bytes=saved)

    def _finish_loss(self, loss_out):
        """Unpack the step's loss output; with a compiled-in scaler, replay
        the eager GradScaler state machine on the device found-inf flag;
        with the health plane compiled in, run the sampled host check."""
        if not isinstance(loss_out, dict):
            return loss_out
        if self._scaler_on:
            # one host sync per step — the same sync the eager scaler's
            # bool(all(isfinite)) already pays
            found = bool(loss_out["found_inf"])
            if found:
                # the executable discarded the update; un-advance the step
                # counter so bias correction replays this step number,
                # exactly as the eager path where optimizer.step() never ran
                self._opt._rollback_step()
                # a skipped update is exactly the kind of step a
                # post-mortem wants whole: force it past head sampling
                self._cur_trace.event("skip_update",
                                      microbatches=self._acc_steps)
                self._cur_trace.escalate("skip_update")
                mon = _monitor._active
                if mon is not None:
                    mon.update_skipped(self._acc_steps)
            self._scaler._compiled_outcome(found)
        if "health" in loss_out:
            self._health_tick(loss_out["loss"], loss_out["health"])
        return loss_out["loss"]

    def _health_tick(self, loss_dev, payload):
        """The host half of the health plane. The device stat block rides
        EVERY step's outputs (it is just more output buffers — nothing
        synced); only every ``PADDLE_HEALTH_SAMPLE``-th step pulls it and
        runs the checks, so the steady-state step stays sync-free."""
        self._health_n += 1
        mon = _monitor._active
        spec = self._health_spec
        if mon is None or spec is None \
                or not mon.health.should_sample(self._health_n):
            return
        host = jax.device_get(payload)
        loss_val = float(jax.device_get(loss_dev))
        mon.health.on_sample(
            spec, self._health_n, loss_val, host,
            named_params=list(zip(self._param_names, self._params)))

    def rollback_last_commit(self, directory: str, before_step=None):
        """Quarantine-the-spike-step restore for raw training loops: load
        the newest snapshot committed strictly BEFORE ``before_step`` (any
        committed snapshot when None), leaving newer — possibly poisoned —
        snapshots on disk untouched. The natural ``rollback_on_spike`` hook
        target when not using hapi's AutoCheckpoint:

            mon.health.rollback_hook = lambda step, info: \\
                step_fn.rollback_last_commit(ckpt_dir, before_step=step)

        Returns the checkpoint info dict or None when nothing older exists.
        The restore lands on the live arrays' placements, so the fast
        path's AOT executables stay valid (arrays re-adopted, no rebuild)."""
        from ..distributed.checkpoint import load_checkpoint
        self.wait_checkpoint()
        max_step = None if before_step is None else int(before_step) - 1
        return load_checkpoint(directory, model=self._model,
                               optimizer=self._opt,
                               grad_scaler=self._scaler,
                               max_step=max_step)

    # --------------------------------------------------------- checkpointing

    def save_checkpoint(self, directory: str, step: int, extra=None,
                        keep: int = 3, block: bool = False,
                        coordinator=None):
        """Snapshot model + optimizer (+ compiled-in GradScaler) through the
        fault-tolerant checkpoint subsystem — the raw-loop counterpart of
        ``hapi.callbacks.AutoCheckpoint``. Async by default (``block=False``):
        state is snapshotted to host now (sharded arrays staged PER SHARD),
        written in the background, at most one save in flight; a prior write
        error surfaces on the next call. ``block=True`` is the
        emergency-save form (e.g. after ``PreemptionWatcher.requested()``).
        ``coordinator``: a ``reshard.PodCommit`` for multi-rank jobs sharing
        one directory (defaults from the launcher env) — the COMMIT manifest
        then lands pod-wide, only after every rank's payload is durable."""
        from ..distributed.checkpoint import AsyncCheckpointer
        ckptr = getattr(self, "_ckptr", None)
        if ckptr is None or ckptr.directory != directory:
            if ckptr is not None:
                ckptr.close()
            ckptr = AsyncCheckpointer(directory, keep=keep,
                                      coordinator=coordinator)
            self._ckptr = ckptr
        ckptr.keep = keep
        ckptr.save(step, model=self._model, optimizer=self._opt,
                   grad_scaler=self._scaler, extra=extra, block=block)

    def wait_checkpoint(self):
        """Barrier for an in-flight async save (surfaces write errors)."""
        ckptr = getattr(self, "_ckptr", None)
        if ckptr is not None:
            ckptr.wait()

    def load_checkpoint(self, directory: str, step=None):
        """Resume model/optimizer/scaler from the newest committed snapshot
        (falling back past torn/corrupt ones); returns the checkpoint info
        dict ({'step': N, ...}) or None when nothing is loadable.

        A snapshot saved at a DIFFERENT world size reshards transparently:
        per-shard payloads land directly on the live arrays' placements
        (this TrainStep's mesh commitment from __init__), so the fast path's
        AOT executables stay valid — ``info["reshard"]`` carries what the
        load did (index-mapped vs gathered arrays, bytes read)."""
        from ..distributed.checkpoint import load_checkpoint
        return load_checkpoint(directory, model=self._model,
                               optimizer=self._opt, step=step,
                               grad_scaler=self._scaler)

    # ------------------------------------------------------------- fast path

    def _input_sig(self, input_arrays):
        return tuple((a.shape, a.dtype.name, a.sharding) for a in input_arrays)

    def _build_fast(self, input_arrays):
        """AOT-compile for this input signature and seed the flat arg state.

        `lower().compile()` pins ONE executable per shape bucket; the per-step
        dispatch then skips jit's trace-cache machinery entirely and, because
        the previous step's output pytree is reused verbatim as the next
        step's inputs, skips the per-param tuple/dict rebuild too.
        """
        if self._compiled is None:
            self._build(input_arrays)
        if self._fast_state is not None:
            # adding a bucket to a live fast path: lower from the ADOPTED
            # state (same placements as the existing executables), not from
            # the live objects — a user-installed array with drifted sharding
            # has already been restored/dropped by _refresh_fast_state, and
            # re-gathering here would seed this bucket with a layout the
            # older buckets were never lowered for
            args = (*self._fast_state, self._step_scalars())
        else:
            args = self._gather_args()
        _remat.reset_trace_stats()
        # the step that paid the compile carries it as its own span, linked
        # to the recompile-sentinel payload by bucket count
        with _trace.span("train_step/compile", path="aot",
                         bucket=len(self._fast) + 1) as comp:
            exe = self._compiled.lower(*args, input_arrays).compile()
        compile_s = comp.dur_s
        sig = self._input_sig(input_arrays)
        self._fast[sig] = exe
        self._fast_bucket[id(exe)] = len(self._fast)
        mon = _monitor._active
        if mon is not None:
            # recompile sentinel: new AOT shape bucket — event carries the
            # offending signature, compile wall-time, running executable
            # count, and the executable's memory_analysis() as HBM gauges
            mon.train_step_compiled(sig, self._mon_prev_sig, compile_s,
                                    len(self._fast), "aot", compiled=exe,
                                    **self._flop_kwargs(input_arrays))
            if self._acc_steps > 1:
                mon.accum_config(self._acc_steps, self._grad_acc_bytes())
            self._emit_shard_gauges(mon)
            self._emit_remat_gauges(mon, compiled=exe,
                                    baseline_args=(args, input_arrays))
        if self._fast_meta is None:
            opt = self._opt
            self._fast_meta = [
                (p, id(p), p.trainable, id(p) in opt._master_weights)
                for p in self._params]
        # [params, masters, states, buffers] — updated in place each step
        self._fast_state = list(args[:4])
        # _gather_args already advanced the optimizer's step scalars for this
        # step; the first execution must use them, not advance again
        return exe, args[4]

    def _readopt(self, new, old):
        """Adopt a user-installed array into the fast state. When its sharding
        differs from the compiled placement (``set_state_dict`` restoring a
        checkpoint laid out for a different mesh, ``.to(device)`` moves), the
        AOT executable would reject it — ``device_put`` it back to the
        placement the executable was lowered for. Raises _PlacementDropNeeded
        when that transfer is impossible (e.g. non-addressable target), which
        drops the stale executables instead of failing the step."""
        if old is None or isinstance(old, tuple) or new is old:
            return new
        try:
            same = new.sharding == old.sharding
        except Exception:
            return new
        if same:
            return new
        mon = _monitor._active
        try:
            moved = jax.device_put(new, old.sharding)
        except Exception as e:
            raise _PlacementDropNeeded(str(e)) from e
        if mon is not None:
            mon.placement_restored()
        return moved

    def _drop_fast_executables(self, why: str):
        """Forget every AOT executable + the flat arg state; the next call
        re-lowers against the live placements (recompile sentinel fires)."""
        n = len(self._fast)
        self._fast.clear()
        self._fast_bucket.clear()
        self._fast_state = None
        self._compiled = None
        mon = _monitor._active
        if mon is not None:
            mon.fast_state_dropped(why, n, step_id=self._gp_id)

    def _refresh_fast_state(self) -> bool:
        """Re-adopt any array a user replaced between steps (set_state_dict,
        eager ops on params/rng). Identity checks only — O(n) `is`, no dict
        or tuple construction on the no-change path. Replacement arrays whose
        sharding no longer matches the compiled placement are device_put back
        (see _readopt); returns False when the executables had to be dropped
        instead (caller must rebuild)."""
        try:
            return self._refresh_fast_state_impl()
        except _PlacementDropNeeded as e:
            self._drop_fast_executables(str(e))
            return False

    def _refresh_fast_state_impl(self) -> bool:
        st = self._fast_state
        params_t, masters_t, states_t, buffers_t = st
        opt = self._opt
        dirty_p = dirty_m = dirty_s = False
        for i, (p, pid, trainable, has_master) in enumerate(self._fast_meta):
            if p._data is not params_t[i]:
                if not dirty_p:
                    params_t = list(params_t)
                    dirty_p = True
                params_t[i] = self._readopt(p.value(), params_t[i])
            if trainable and opt._accumulators[pid] is not states_t[i]:
                if not dirty_s:
                    states_t = list(states_t)
                    dirty_s = True
                old = states_t[i]
                states_t[i] = {name: self._readopt(
                                   opt._accumulators[pid][name],
                                   old.get(name))
                               for name in opt._state_names}
            if has_master and opt._master_weights[pid] is not masters_t[i]:
                if not dirty_m:
                    masters_t = list(masters_t)
                    dirty_m = True
                masters_t[i] = self._readopt(opt._master_weights[pid],
                                             masters_t[i])
        if dirty_p:
            st[0] = tuple(params_t)
        if dirty_m:
            st[1] = tuple(masters_t)
        if dirty_s:
            st[2] = tuple(states_t)
        for i, b in enumerate(self._buffers):
            if b._data is not buffers_t[i]:
                old = buffers_t[i]
                if not isinstance(buffers_t, list):
                    buffers_t = list(buffers_t)
                buffers_t[i] = self._readopt(b.value(), old)
        if isinstance(buffers_t, list):
            st[3] = tuple(buffers_t)
        return True

    def _fast_prepare(self, input_arrays):
        """(executable, step scalars, signature) for these inputs."""
        sig = self._input_sig(input_arrays)
        exe = self._fast.get(sig)
        if exe is None:
            # re-adopt user-installed arrays BEFORE lowering a new bucket so
            # every bucket shares one placement story (on drop, _fast_state
            # clears and the build gathers fresh)
            if self._fast_state is not None:
                self._refresh_fast_state()
            exe, scalars = self._build_fast(input_arrays)
        elif not self._refresh_fast_state():
            # placement drift dropped the executables: rebuild for this
            # signature against the live layout
            exe, scalars = self._build_fast(input_arrays)
        else:
            scalars = self._step_scalars()
        return exe, scalars, sig

    def _fast_dispatch(self, exe, scalars, sig, input_arrays, host_t0):
        opt = self._opt
        mon = _monitor._active
        if mon is not None:
            self._mon_prev_sig = sig
        st = self._fast_state
        bucket = self._fast_bucket[id(exe)]
        with _trace.span("train_step/dispatch", path="aot",
                         bucket=bucket) as d:
            loss_out, new_params, new_masters, new_states, new_buffers = exe(
                st[0], st[1], st[2], st[3], scalars, input_arrays)
        if mon is not None:
            mon.step_event(d.dur_s,
                           microbatches=self._microbatches(input_arrays),
                           bucket=bucket, span=(d.t0, d.t1),
                           host_t0=host_t0, step_id=self._gp_id)

        # outputs become next step's inputs verbatim (donation-friendly: the
        # just-invalidated input buffers are replaced wholesale)
        st[0], st[1], st[2], st[3] = (new_params, new_masters, new_states,
                                      new_buffers)
        # write-through so eager reads (state_dict, checkpoints, interleaved
        # eval) observe the step; output pytrees are fresh per call, so
        # assigning without copying is safe
        acc = opt._accumulators
        mw = opt._master_weights
        for (p, pid, trainable, has_master), a, m, s in zip(
                self._fast_meta, new_params, new_masters, new_states):
            p._data = a
            if trainable:
                acc[pid] = s
            if has_master:
                mw[pid] = m
        for b, a in zip(self._buffers, new_buffers):
            b._data = a
        return Tensor(self._finish_loss(loss_out))
