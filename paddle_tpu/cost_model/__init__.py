"""Op-level cost model for traced programs.

Reference analog: python/paddle/cost_model/cost_model.py — profiles a static
program per-op and exposes measured time/memory so planners (auto-parallel,
pipeline segmentation) can cost candidate placements; the C++ side keeps
static per-op benchmark tables.

TPU-native redesign: the "program" is a traced jaxpr. Costs come from an
analytic roofline over the device's peak FLOP/s and HBM bandwidth — FLOPs
from dot/conv dimension math, bytes from operand/result avals — optionally
calibrated by measuring the compiled executable. This is the same split the
reference makes (static table + profiler refinement), with XLA's jaxpr
replacing ProgramDesc.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["CostModel", "OpCost", "DeviceSpec", "TPU_V4", "TPU_V5E",
           "HOST_CPU"]


@dataclass
class DeviceSpec:
    """Peak numbers the roofline is computed against."""
    name: str
    peak_flops: float          # FLOP/s at the matmul dtype
    hbm_bandwidth: float       # bytes/s
    vmem_bytes: int = 16 * 2 ** 20


# one v4 chip: ~275 TFLOP/s bf16, ~1.2 TB/s HBM
TPU_V4 = DeviceSpec("tpu-v4", peak_flops=275e12, hbm_bandwidth=1.2e12)
# one v5e chip: 197 TFLOP/s bf16, 819 GB/s HBM (Google Cloud docs, "TPU v5e")
TPU_V5E = DeviceSpec("tpu-v5e", peak_flops=197e12, hbm_bandwidth=819e9)
HOST_CPU = DeviceSpec("cpu", peak_flops=1e11, hbm_bandwidth=5e10)

# TPU specs by jax ``device_kind`` prefix. A kind that is not here is an
# error, never costed as some other chip.
_TPU_SPECS = {"TPU v4": TPU_V4, "TPU v5 lite": TPU_V5E}


def device_spec(device) -> DeviceSpec:
    """The roofline peaks for one jax device."""
    if device.platform != "tpu":
        return HOST_CPU
    for prefix, spec in _TPU_SPECS.items():
        if device.device_kind.startswith(prefix):
            return spec
    raise ValueError(
        f"no DeviceSpec for TPU device_kind {device.device_kind!r} "
        f"(known: {sorted(_TPU_SPECS)}); add its published peaks to "
        f"cost_model._TPU_SPECS or pass CostModel(device=DeviceSpec(...))")


@dataclass
class OpCost:
    op: str
    flops: float
    bytes: float
    time: float                # roofline seconds: max(flops/peak, bytes/bw)
    shape: str = ""


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape) * aval.dtype.itemsize)
    except Exception:
        return 0.0


def _dot_flops(eqn) -> float:
    # dot_general: 2 * batch * M * N * K
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
    batch = np.prod([lhs.shape[i] for i in lb], initial=1.0)
    k = np.prod([lhs.shape[i] for i in lc], initial=1.0)
    m = np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                 if i not in tuple(lc) + tuple(lb)], initial=1.0)
    n = np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                 if i not in tuple(rc) + tuple(rb)], initial=1.0)
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval           # kernel
    # 2 * output elements * (kernel spatial * in-channels)
    per_out = 2.0 * np.prod(rhs.shape[:-1], initial=1.0)
    return float(np.prod(out.shape)) * per_out


class CostModel:
    """Static (roofline) + measured costs for a jittable fn or jaxpr."""

    def __init__(self, device: Optional[DeviceSpec] = None):
        self.device = device or self._detect()

    @staticmethod
    def _detect() -> DeviceSpec:
        import jax
        return device_spec(jax.devices()[0])

    # -------------------------------------------------------------- static

    def static_cost(self, fn: Callable = None, *args,
                    jaxpr=None) -> Tuple[List[OpCost], float]:
        """Per-op roofline costs + total seconds for one execution.

        Pass either (fn, *example_args) — traced here — or a ClosedJaxpr.
        Nested jaxprs (scan/cond/pjit bodies) are costed recursively; scan
        bodies multiply by the trip count."""
        import jax
        if jaxpr is None:
            jaxpr = jax.make_jaxpr(fn)(*args)
        rows: List[OpCost] = []
        self._walk(jaxpr.jaxpr, rows, mult=1.0)
        total = sum(r.time for r in rows)
        return rows, total

    def _walk(self, jaxpr, rows: List[OpCost], mult: float):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in ("scan", "while", "cond", "pjit", "custom_vjp_call",
                        "custom_jvp_call", "remat", "checkpoint",
                        "custom_vjp_call_jaxpr", "shard_map"):
                inners = self._inner_jaxprs(eqn)
                if inners:
                    for inner, n in inners:
                        self._walk(inner, rows, mult * n)
                    continue
            flops = 0.0
            if prim == "dot_general":
                flops = _dot_flops(eqn)
            elif prim == "conv_general_dilated":
                flops = _conv_flops(eqn)
            else:
                # elementwise-ish: one FLOP per output element
                flops = sum(float(np.prod(o.aval.shape))
                            for o in eqn.outvars if hasattr(o.aval, "shape"))
            byts = (sum(_aval_bytes(v.aval) for v in eqn.invars
                        if hasattr(v, "aval"))
                    + sum(_aval_bytes(o.aval) for o in eqn.outvars))
            t = max(flops / self.device.peak_flops,
                    byts / self.device.hbm_bandwidth) * mult
            shape = ",".join(str(tuple(getattr(o.aval, "shape", ())))
                             for o in eqn.outvars)
            rows.append(OpCost(prim, flops * mult, byts * mult, t, shape))

    @staticmethod
    def _inner_jaxprs(eqn) -> List[Tuple[Any, float]]:
        """Every nested jaxpr with its execution multiplier. A while loop
        costs cond + body once each (the trip count is data-dependent; the
        roofline reports one iteration, like the reference's per-op table)."""
        p = eqn.params
        n = float(p["length"]) if "length" in p else 1.0  # scan trip count
        out: List[Tuple[Any, float]] = []
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                    "body_jaxpr"):
            if key in p:
                j = p[key]
                out.append(((j.jaxpr if hasattr(j, "jaxpr") else j), n))
        if not out and "branches" in p:        # cond: cost the first branch
            out.append((p["branches"][0].jaxpr, n))
        return out

    # ------------------------------------------------------------ measured

    def profile_measure(self, fn: Callable, *args, iters: int = 5,
                        warmup: int = 2) -> Dict[str, float]:
        """Measured wall time of the compiled fn (reference
        cost_model.profile_measure runs the program under the profiler)."""
        import jax
        jitted = jax.jit(fn)
        for _ in range(warmup):
            jax.block_until_ready(jitted(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        rows, est = self.static_cost(fn, *args)
        return {"measured_time": dt, "static_time": est,
                "flops": sum(r.flops for r in rows),
                "bytes": sum(r.bytes for r in rows),
                "mfu": (sum(r.flops for r in rows)
                        / (dt * self.device.peak_flops)) if dt > 0 else 0.0}

    # ---------------------------------------------------------- aggregates

    def summary(self, rows: List[OpCost], top: int = 10) -> str:
        rows = sorted(rows, key=lambda r: -r.time)[:top]
        lines = [f"{'op':<24}{'flops':>14}{'bytes':>14}{'us':>10}  shape"]
        for r in rows:
            lines.append(f"{r.op:<24}{r.flops:>14.3g}{r.bytes:>14.3g}"
                         f"{r.time * 1e6:>10.1f}  {r.shape[:40]}")
        return "\n".join(lines)
