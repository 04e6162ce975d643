"""The one file that touches the program: builds the system under test
(`jit.TrainStep`, `io.DeviceLoader`, `serving.DecodeEngine` round the model
the cell's family builds) from a configuration file and the benchmark's
own weights, and reads its public counters. What knows a model's leaves,
shapes or arithmetic is the family's (`families/<family>.py`, reached
through `cell.family`); everything measured or compared lives in the
benchmark's other files.
"""
from __future__ import annotations

import gc
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from . import sketch as SK


def compare_map(fam, model: dict) -> dict:
    """{comparison leaf: (reference norm key, layer)}: the program's
    leaves, a fused one (`fam.FUSED`) split into its parts."""
    out = {}
    for name, (key, layer) in fam.leaf_map(model).items():
        if key in fam.FUSED:
            for part in fam.FUSED[key]:
                out[f"{name}.{part}"] = (f"{key}.{part}", layer)
        else:
            out[name] = (key, layer)
    return out


def _leaf_norms(parts_of: dict, arrays) -> dict:
    """L2 norm of each program leaf, a fused one by its parts (equal
    slices of the last axis); `parts_of` is {leaf name: part names or
    ()}, in the arrays' order. One jitted call, scalars fetched once."""
    def sq(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def norms(xs):
        out = []
        for parts, x in zip(parts_of.values(), xs):
            if parts:
                h = x.shape[-1] // len(parts)
                out += [sq(x[..., i * h:(i + 1) * h])
                        for i in range(len(parts))]
            else:
                out.append(sq(x))
        return out

    keys = []
    for n, parts in parts_of.items():
        keys += [f"{n}.{p}" for p in parts] if parts else [n]
    return dict(zip(keys, map(float, jax.jit(norms)(arrays))))


def enable_compile_cache() -> str:
    from paddle_tpu.utils.compile_cache import enable_compile_cache as on
    return on()


@contextmanager
def _cheap_init():
    """The program samples every initial weight on the host, matrices
    twice (`nn/initializer/api.py::_host_sample`): a minute of set-up at
    1.3B, for values the benchmark's arrays replace at once. While the
    model is built that sampler hands back zeros. Where the program no
    longer has it, nothing is swapped and set-up is merely slower
    (PERF.md, Open questions: the program should offer this itself)."""
    try:
        from paddle_tpu.nn.initializer import api
        orig = api._host_sample
    except (ImportError, AttributeError):
        yield
        return
    api._host_sample = lambda sampler, shape, dtype: jnp.zeros(shape, dtype)
    try:
        yield
    finally:
        api._host_sample = orig


def build_model(fam, cfg: dict, arrays: dict):
    """The family's model at the configuration's sizes, every leaf
    replaced by the benchmark's array for it (so the program's own
    initialiser decides nothing)."""
    with _cheap_init():
        model = fam.build(cfg)
    assign(fam, model, cfg["model"], arrays)
    return model


def assign(fam, model, model_cfg: dict, arrays: dict):
    lm = fam.leaf_map(model_cfg)
    seen = set()
    for name, p in model.named_parameters():
        key, layer = lm[name]
        a = arrays[key] if layer is None else arrays[key][layer]
        if tuple(a.shape) != tuple(p.shape):
            raise ValueError(f"{name}: program leaf {tuple(p.shape)} vs "
                             f"benchmark array {tuple(a.shape)}")
        p._data = a
        seen.add(name)
    missing = set(lm) - seen
    if missing:
        raise ValueError(f"program model lacks leaves {sorted(missing)[:4]}")


class Trainer:
    """DataLoader -> DeviceLoader -> TrainStep over the benchmark's rows,
    as the job file states it. `batches()` yields device batches; `step`
    dispatches one optimizer update and returns the loss handle."""

    def __init__(self, cell, seed: int, rows):
        import paddle_tpu as paddle
        from paddle_tpu.io import DataLoader, Dataset, DeviceLoader
        cfg, job = cell.config, cell.mix
        self.fam, self.model_cfg, self.job = cell.family, cfg["model"], job
        self.seed, self.dtype = seed, cfg["dtype"]
        opt_cfg = job["optimizer"]
        arrays = self.fam.make(self.model_cfg, seed, self.dtype)
        self.model = build_model(self.fam, cfg, arrays)
        del arrays
        if job.get("recompute", "none") != "none":
            self.model.enable_recompute(job["recompute"])
        opt = paddle.optimizer.AdamW(
            learning_rate=opt_cfg["lr"], beta1=opt_cfg["beta1"],
            beta2=opt_cfg["beta2"], epsilon=opt_cfg["eps"],
            weight_decay=opt_cfg["weight_decay"],
            parameters=self.model.parameters(), multi_precision=True)
        self.opt = opt
        k = int(job.get("accumulate_steps", 1))
        self.step_fn = paddle.jit.TrainStep(
            self.model, opt, accumulate_steps=k if k > 1 else None)
        micro = job["batch"] // k

        class Rows(Dataset):
            def __len__(self):
                return 1 << 24

            def __getitem__(self, i):
                r = rows(i)
                return r, r

        self.loader = DeviceLoader(
            DataLoader(Rows(), batch_size=micro, shuffle=False),
            prefetch_depth=2, stack_batches=k)

    def batches(self):
        return iter(self.loader)

    def step(self, batch):
        return self.step_fn(*batch)

    # ---- state, read through the optimizer's public state_dict()

    def _state(self):
        """(state_dict, {leaf name: its key there}); the optimizer keys
        its state in the order of the model's parameters."""
        sd = self.opt.state_dict()
        keys = [k[:-len("_moment1")] for k in sd if k.endswith("_moment1")]
        names = [n for n, _ in self.model.named_parameters()]
        if len(keys) != len(names):
            raise ValueError("optimizer state does not cover every leaf")
        return sd, dict(zip(names, keys))

    def _norms(self, names, arrays) -> dict:
        lm = self.fam.leaf_map(self.model_cfg)
        return _leaf_norms({n: self.fam.FUSED.get(lm[n][0], ())
                            for n in names}, arrays)

    def _masters(self, sd, key_of) -> list:
        """The fp32 value the optimizer updates: its master copy, or the
        parameter itself where that is already float32."""
        live = dict(self.model.named_parameters())
        return [sd["master_weights"][k].value()
                if k in sd["master_weights"] else live[n].value()
                for n, k in key_of.items()]

    def first_grad_norms(self) -> dict:
        """Per-leaf norm of the gradient the optimizer was handed in its
        FIRST update, worked out from Adam's first moment after that one
        step: m1 = (1 - beta1) * g."""
        sd, key_of = self._state()
        b1 = self.job["optimizer"]["beta1"]
        m = [sd[f"{key_of[n]}_moment1"].value() for n in key_of]
        return {n: v / (1.0 - b1)
                for n, v in self._norms(key_of, m).items()}

    def first_grad_sketches(self) -> dict:
        """{leaf: [K] sketch of its first gradient}, from the same first
        moment (`benchmark/sketch.py`)."""
        sd, key_of = self._state()
        b1 = self.job["optimizer"]["beta1"]
        m = [sd[f"{key_of[n]}_moment1"].value() for n in key_of]

        def all_of(xs):
            pats = {}
            return [SK.sketch(x, pats.setdefault(x.shape, SK.signs(x.shape)))
                    for x in xs]

        out = jax.device_get(jax.jit(all_of)(m))
        return {n: np.asarray(v) / (1.0 - b1) for n, v in zip(key_of, out)}

    def update_norms(self) -> dict:
        """Per-leaf norm of (fp32 master now - the weights it started
        from); the start is made again from the seed."""
        sd, key_of = self._state()
        lm = self.fam.leaf_map(self.model_cfg)
        w0 = self.fam.make(self.model_cfg, self.seed, self.dtype)
        diffs = []
        for n, mst in zip(key_of, self._masters(sd, key_of)):
            key, layer = lm[n]
            a = w0[key] if layer is None else w0[key][layer]
            diffs.append(mst - a.astype(jnp.float32))
        return self._norms(key_of, diffs)

    def num_compiles(self) -> int:
        return int(self.step_fn.num_compiles)

    def temp_bytes(self) -> int:
        """Largest temporary allocation of the step's executables (the
        allocator's peak does not count it: PERF.md section 5)."""
        best = 0
        for exe in getattr(self.step_fn, "_fast", {}).values():
            ma = exe.memory_analysis()
            best = max(best, int(getattr(ma, "temp_size_in_bytes", 0)))
        return best

    def close(self):
        self.loader.close()
        self.loader = self.step_fn = self.opt = self.model = None
        gc.collect()


class Server:
    """`DecodeEngine` over the configuration's deployment."""

    def __init__(self, cell, seed: int, model=None):
        """`model`: a model object kept from an earlier Server of the same
        configuration (the tools' several seeds in one process); it gets
        this seed's weights and a new engine."""
        from paddle_tpu.serving import DecodeEngine
        cfg, fam = cell.config, cell.family
        arrays = fam.make(cfg["model"], seed, cfg["dtype"])
        if model is None:
            self.model = build_model(fam, cfg, arrays)
        else:
            self.model = model
            assign(fam, model, cfg["model"], arrays)
        del arrays
        self.model.eval()
        self.geometry = dict(cfg["engine"])
        self.engine = DecodeEngine(self.model, paged=True, **self.geometry)
        self.max_slots = self.engine.max_slots

    def warm(self, prompt, new_tokens: int):
        """One request through chunked prefill and decode mints the
        cell's two executables; its parked blocks are dropped after."""
        self.engine.submit(list(prompt), max_new_tokens=new_tokens)
        self.engine.run()
        self.engine.drop_prefix_cache()

    def submit(self, prompt, new_tokens):
        return self.engine.submit(prompt, max_new_tokens=new_tokens)

    def step(self):
        return self.engine.step()

    def busy(self) -> int:
        """Requests admitted or waiting inside the engine."""
        return self.engine.active_count + self.engine.queue_depth

    def live(self) -> int:
        return self.engine.live_count

    def counters(self) -> dict:
        st = self.engine.stats()
        pg = st.get("paged", {})
        return {"compile_count": st["compile_count"],
                "decode_steps": st["decode_steps"],
                "tokens_generated": st["tokens_generated"],
                "preemptions": pg.get("preemptions", 0),
                "shared_tokens": pg.get("shared_tokens", 0),
                "prefix_hit_tokens": pg.get("prefix_hit_tokens", 0),
                "prefix_hits": pg.get("prefix_hits", 0),
                "nan_logits": st["guardrails"]["nan_logits"]}

    def temp_bytes(self) -> int:
        best = 0
        exes = [getattr(self.engine, "_decode_exe", None)]
        exes += list(getattr(self.engine, "_prefill_exes", {}).values())
        for exe in exes:
            if exe is not None:
                ma = exe.memory_analysis()
                best = max(best, int(getattr(ma, "temp_size_in_bytes", 0)))
        return best

    def close(self):
        self.engine.close()
        self.engine = self.model = None
        gc.collect()


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_live_bytes() -> int:
    return max((int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in jax.local_devices()), default=0)


def memory_peak_bytes() -> int:
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak
