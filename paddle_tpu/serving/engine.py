"""Compiled decode engine: block-paged KV cache + continuous batching.

The serving analog of ``jit.TrainStep``: every hot-path computation is an
AOT executable (``jax.jit(...).lower().compile()``) minted ONCE per shape
bucket, and the steady state runs zero recompiles no matter which requests
come and go.

The memory model is a **block page table** (vLLM, Kwon et al. 2023): the
KV pool is per-layer ``[kv_blocks, block_size, n_kv,
hd]`` K/V pairs plus a fixed-shape ``[max_slots, max_blocks_per_slot]``
int32 block-index table. Which physical block backs which logical position
is table DATA, never executable shape — admissions, evictions, block
allocation, prefix sharing and copy-on-write all leave the compiled
programs untouched. A host-side ``pager.BlockPager`` owns the free list,
refcounts, hash-keyed shared prefix blocks and COW decisions; the device
copies a COW needs ride INTO the next decode/chunk call as ``(src, dst)``
index arguments (padded with trash-block pairs), so COW costs no extra
executable and no extra dispatch. Executable families:

* **decode step** — fixed shape ``[max_slots, 1]``: one token for every
  slot, each row reading its K/V through the block table (``jnp.take`` on
  the block axis) and writing at its own cursor. One compile, ever.
* **chunk prefill** — ONE executable of shape ``[1, prefill_chunk]``
  (decode-shaped: same pool + table machinery, serves any prompt length):
  each scheduler iteration feeds at most ``prefill_chunk`` prompt tokens
  of the admitting request through it, so a 2k-token prompt admits over
  several steps instead of freezing every live slot behind a monolithic
  prefill (Sarathi-Serve). ``prefill_chunk=None`` falls back to one
  bucketed whole-prompt chunk per admission (monolithic; one executable
  per prompt-length bucket, the PR 6 scheduling behavior).

**Tensor-parallel decode**: when ``distributed.env.get_mesh()`` has a
"model" axis of degree > 1 AND the model rides it (shard_gpt_tp /
shard_llama_tp / mp_layers), the same executables mint as SPMD programs —
each KV pool placed ``NamedSharding(mesh, P(None, None, "model", None))``
(head-sharded; head_dim fallback when GQA's ``n_kv % tp != 0``), weights
on their Column/RowParallel placements, and the block table / cursors /
token ids / COW pairs committed mesh-REPLICATED host data, so the
``BlockPager`` never learns about the mesh and the zero-recompile
contract survives block churn on it.

The pager's **persistent prefix cache** outlives tenants: registered
prompt blocks park in an LRU at refcount zero and later same-prefix
requests re-adopt them with zero prefill compute; the free list reclaims
parked blocks (oldest first) before any live tenant is preempted.

Pools/buffers are donated through every call so XLA updates them in place;
steady-state decode allocates nothing. Stale K/V from a slot's previous
tenant is harmless by construction: causal masking only exposes positions
``<= cursor``, and every position below the cursor was freshly written by
this tenant's prefill or decode steps.

Int8 weight-only quantization (``quantize="int8"``) swaps the model's
Linear layers for ``quantization.Int8Linear`` (dynamic per-token activation
scales) IN PLACE before tracing — the engine then serves int8 GEMMs with
fp accumulation, same executables, same zero-recompile contract.
"""
from __future__ import annotations

import itertools
import os
import time
from collections import OrderedDict
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import monitor as _monitor
from ..monitor import trace as _trace
from ..core.tensor import Tensor
from ..distributed.env import get_mesh
from ..models.cache_spec import ModelSpec, pool_lanes
from ..models.gpt import (_lm_head_logits, _pick_token,
                          _resolve_decode_horizon, set_paged_kv_sharding)
from ..distributed.reshard import snapshot as _snapshot
from ..kernels.pallas import pool_write
from ..kernels.pallas.util import attention_kernels_traced, pool_writes_traced
from .guardrails import (HANG_ENV, DispatchWatchdog, EngineHangError,
                         FaultSchedule, InjectedFault)
from .pager import TRASH_BLOCK, BlockPager, prefix_digest
from .scheduler import (TERMINAL_STATUSES, AdmissionQueue, Request,
                        SlotAllocator)

__all__ = ["DecodeEngine", "Request", "generate_via_engine",
           "quantize_for_serving", "EngineHangError", "TERMINAL_STATUSES"]


# cache entries whose content is blocks behind the block table (the pager's
# blocks, copy-on-write and the prefix cache apply); a "state" entry is rows
_PAGED = ("kv", "latent")

# terminal caller-supplied request ids remembered per engine for dedup
# (a requeue retry arriving AFTER completion still returns the original)
DEDUP_WINDOW = 1024


def _rides_model_axis(arr) -> bool:
    """True when ``arr`` carries a NamedSharding partitioned over the
    "model" mesh axis (the signal that someone ran shard_gpt_tp /
    shard_llama_tp / the mp_layers on this model)."""
    sh = getattr(arr, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return False
    for part in sh.spec:
        if part == "model" or (isinstance(part, (tuple, list))
                               and "model" in part):
            return True
    return False


def serving_mesh(leaves):
    """The engine's tensor-parallel activation rule: the global mesh has a
    "model" axis of degree > 1 AND the model actually rides it (at least
    one param/buffer sharded over that axis). A replicated model on a
    model-axis mesh stays single-chip — the mesh alone proves nothing
    about THIS model (another test or tenant may have built it)."""
    mesh = get_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] <= 1:
        return None, 1
    if not any(_rides_model_axis(t.value()) for t in leaves):
        return None, 1
    return mesh, int(mesh.shape["model"])


def _model_spec(model) -> ModelSpec:
    """What the model says of itself (``models/cache_spec.py``): the
    cached-forward backbone, the LM head, and per layer what it caches
    (``kv`` with heads and width, ``state`` with its arrays, or several
    such entries). The engine asks nothing else about the architecture."""
    describe = getattr(model, "decode_spec", None)
    if describe is None:
        raise TypeError(
            f"{type(model).__name__} has no decode_spec() - the engine "
            f"serves causal LMs that describe their backbone and per-layer "
            f"caches (models/cache_spec.py)")
    return describe()


def quantize_for_serving(model, skip: Sequence = ()):
    """Weight-only int8 conversion of every ``nn.Linear`` IN PLACE (the
    ``QAT.quantize`` idiom): per-output-channel int8 weights + dynamic
    per-token activation scales, int8 MXU dot with fp32 accumulation.

    The LM head is always skipped — the engine's head matmul reads the raw
    weight array (tied-embedding compatible), and head logits are the most
    quantization-sensitive tensor in the model anyway. ``skip`` adds
    further layer objects (by identity) to leave untouched."""
    from ..nn import Linear
    from ..nn.layer import swap_sublayers
    from ..quantization import Int8Linear

    keep = {id(s) for s in skip if s is not None}
    head = getattr(model, "lm_head", None)
    if head is not None:
        keep.add(id(head))

    def swap(layer):
        if isinstance(layer, Linear) and id(layer) not in keep:
            return Int8Linear.from_linear(layer)
        return None

    return swap_sublayers(model, swap)


class _PrefillState:
    """One slot's in-flight chunked prefill: which prompt positions are
    cached so far (shared-prefix coverage counts), which are covered by
    the chunks launched so far (one more chunk than ``done`` while a step
    is on the device) and the pending COW copies the next chunk call must
    apply."""

    __slots__ = ("req", "prompt", "n", "done", "sent", "pending_copies",
                 "prefill_s", "chunks")

    def __init__(self, req: Request, start: int,
                 pending_copies: List[tuple]):
        self.req = req
        self.prompt = np.asarray(req.prompt, np.int32)
        self.n = len(req.prompt)
        self.done = int(start)            # positions already cached
        self.sent = int(start)            # ... once what is launched lands
        self.pending_copies = list(pending_copies)
        self.prefill_s = 0.0
        self.chunks = 0


class _ChunkCall:
    """One chunk-executable call of a plan: its slot and range, its device
    arguments (made when the plan was), and, once launched, its outputs
    still on the device and its ``engine/prefill_call`` span."""

    __slots__ = ("slot", "st", "sc", "p0", "end", "final", "n_cow", "args",
                 "tok0", "ok", "span")

    def __init__(self, slot, st, sc, p0, end, n_cow, args):
        self.slot, self.st, self.sc = slot, st, sc
        self.p0, self.end, self.final = p0, end, end >= st.n
        self.n_cow, self.args = n_cow, args
        self.tok0 = self.ok = self.span = None


class _DecodeCall:
    """The decode-executable call of a plan. ``rows``: {slot: its request}
    for the slots the step advances. ``tok``: the uploaded host tokens, or
    None where the step in flight when the plan was made decodes: its
    picked tokens, still on the device, are this call's. ``firsts``:
    (slot index on the device, chunk call) for the rows whose token is a
    chunk's first one, merged in on the device at launch."""

    __slots__ = ("rows", "tok", "firsts", "args", "attrs", "picked", "ok",
                 "span")

    def __init__(self, rows, tok, firsts, args, attrs):
        self.rows, self.tok, self.firsts = rows, tok, firsts
        self.args, self.attrs = args, attrs
        self.picked = self.ok = self.span = None


class _Plan:
    """One engine step, prepared: the chunk calls in launch order, then
    the decode call (or None), and the sampling keys drawn for them, which
    a plan that is thrown away hands to the one built in its place."""

    __slots__ = ("chunks", "decode", "keys")

    def __init__(self, chunks, decode, keys):
        self.chunks, self.decode, self.keys = chunks, decode, keys


class DecodeEngine:
    """AOT-compiled serving engine over one causal LM.

    Knobs:
      max_slots        batch rows of the decode step (concurrent requests)
      max_len          per-slot KV horizon; prompt + new tokens must fit
      block_size       tokens per KV block
      kv_blocks        physical pool size incl. the reserved trash block;
                       default max_slots*ceil(max_len/block_size)+1 (every
                       slot at its full horizon) — set it SMALLER to
                       oversubscribe (prefix sharing is what makes that safe)
      prefill_chunk    at most this many prompt tokens run per scheduler
                       iteration through ONE [1, chunk] executable (None:
                       whole-prompt bucketed chunks, monolithic)
      prefill_buckets  padded prompt lengths for monolithic prefill (one
                       executable each); default: powers of two up to
                       max_len; unused when prefill_chunk is set
      max_queue        admission-queue bound; a full queue rejects at the
                       door with status="rejected_overload" (None: unbounded)
      quantize         None | "int8" (weight-only, converts model in place)
      do_sample/temperature/top_k/seed
                       sampling config — STATIC per engine (baked into the
                       executables); greedy by default
      hang_s           dispatch-watchdog threshold in seconds (default:
                       env PADDLE_SERVE_HANG_S; 0/unset = off — CPU XLA
                       steps legitimately take seconds under load)
      fault_schedule   a guardrails.FaultSchedule, or None to read the
                       PADDLE_SERVE_FAULT env (the chaos seam; production
                       never sets either)
      kv_pool          a ``serving.kvpool`` pool (LocalPool or KVPool over
                       the launch KV master) — the cross-process prefix-
                       cache tier: parked registered blocks export to it
                       and registry-miss admissions fetch + adopt from it
                       (``kvpool.resolve_kv_pool()`` picks by env). None
                       (the default) disables the tier entirely.

    ``submit()`` validates and queues; ``step()`` runs ONE scheduler
    iteration (admit into free slots, advance pending prefill chunks, then
    one decode step over all live slots); ``run()`` drains. A step's calls
    are launched back to back and the NEXT step's (admission, chunk and
    decode set-up, argument uploads) prepared while the device runs them
    (``_step_planned``; a drafter's verify calls are made in turn, after
    the step's chunks). Telemetry lands
    under ``serve/*`` when the monitor is enabled, and every minted
    executable bumps ``compile_count`` (the serving recompile sentinel —
    flat in steady state).

    **Guardrails** (all host-side — no shape, no executable, no parity
    impact when unused): per-request deadlines (``submit(...,
    ttft_deadline_s=, deadline_s=)``, enforced at step boundaries
    including across preemption/requeue and chunked prefill; terminal
    status ``expired``, slot + blocks released exactly once);
    ``cancel(req)`` from queue, mid-prefill or mid-decode (terminal
    ``cancelled``); ``drain(grace_s=)`` / ``begin_drain()`` graceful
    shutdown (door answers ``rejected_draining``, live slots finish or
    expire within grace) with ``drain_on_preemption()`` wiring a
    PreemptionWatcher so SIGTERM drains instead of dying mid-token; a
    dispatch watchdog that WARNs + flight-dumps on a wedged decode/chunk
    call and then fails the engine loudly; and the PADDLE_SERVE_FAULT
    chaos seam that makes every one of those paths deterministically
    testable.
    """

    _ids = itertools.count()

    def __init__(self, model, *, max_slots: int = 8, max_len: int = 256,
                 paged: bool = True, block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_queue: Optional[int] = 1024,
                 quantize: Optional[str] = None, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, seed: int = 0,
                 hang_s: Optional[float] = None,
                 fault_schedule: Optional[FaultSchedule] = None,
                 drafter=None, kv_pool=None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', "
                             f"got {quantize!r}")
        if not paged:
            # the argument stays only until benchmark/system.py, which no
            # PR but a ``benchmark`` one may edit, stops passing paged=True
            raise ValueError(
                "paged=False: the slot-owns-a-row cache was deleted in PR 31 "
                "(the block page table with chunked prefill serves "
                "everything it did); drop the argument")
        spec = _model_spec(model)
        if max_len > spec.max_pos:
            raise ValueError(
                f"max_len {max_len} exceeds the model's position horizon "
                f"({spec.max_pos})")
        if quantize == "int8":
            quantize_for_serving(model)
        self.model = model
        self.spec = spec
        # layers that keep a fixed-size recurrent state per slot (beside or
        # instead of K/V): what they cannot do yet is refused by name
        self._has_state = bool(spec.state_layers)
        self._state_bytes = spec.state_bytes_per_slot
        # ... and layers that cache one latent row a position (blocks
        # behind the table like K/V: the pager serves them by mechanism)
        self._has_latent = bool(spec.latent_layers)
        self.quantize = quantize
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self._do_sample = bool(do_sample)
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        # ---- speculative decoding (spec.py): a drafter guesses k tokens,
        # ONE chunk-shaped verify dispatch scores all of them, the longest
        # agreeing prefix + the bonus token are emitted. Greedy-only: the
        # acceptance rule IS bitwise argmax agreement, so output is exactly
        # what sequential decode would produce.
        self.drafter = drafter
        if drafter is not None:
            if self._has_state:
                raise NotImplementedError(
                    "speculative decoding with recurrent-state layers needs "
                    "a state snapshot to roll rejected drafts back to: the "
                    "verify executable is not built for such a model")
            if self._has_latent:
                raise NotImplementedError(
                    "speculative decoding over latent-attention entries "
                    "needs a verify executable that walks cache entries "
                    "(it hands every layer a K pool and a V pool): not "
                    "built for a model whose layers cache latent rows")
            if self._do_sample:
                raise NotImplementedError(
                    "speculative decoding is greedy-only (acceptance is "
                    "bitwise argmax agreement; do_sample would need a "
                    "rejection-sampling acceptance rule)")
            # verify width: k drafts + 1 carried token per dispatch. Minted
            # ONCE — drafts ride as ids data, never as shape.
            self._spec_width = int(min(
                max(2, int(getattr(drafter, "max_k", 4)) + 1), max_len))
        else:
            self._spec_width = None
        # the executables rebind EVERY param and buffer as an input, so
        # weight updates (or an int8 swap) between calls flow through
        # without retracing
        self._leaves = [p for _, p in model.named_parameters()] \
            + [b for _, b in model.named_buffers()]
        # param count for the goodput plane's analytic 2ND inference FLOP
        # model (fallback + cross-check next to each mint's cost_analysis)
        self._n_params = sum(
            int(np.prod(p.shape)) if p.ndim else 1
            for _, p in model.named_parameters())
        self._cache_dtype = spec.head_weight.value().dtype
        # K and V of one position, every kv entry: what a call span's
        # ``kv_bytes`` counts per live context token
        self._kv_bytes = jnp.dtype(self._cache_dtype).itemsize * (sum(
            2 * c.n_kv_heads * c.head_dim for c in spec.kv_layers)
            + sum(c.head_dim for c in spec.latent_layers))
        # ---- tensor-parallel decode over the device mesh: with a "model"
        # axis of degree > 1 and a model riding it, the executables become
        # SPMD programs — KV pools shard on the head axis (hd fallback for
        # GQA counts the axis can't divide), weights keep their Column/
        # RowParallel placements, and the block table / cursors / COW index
        # arguments stay replicated host data (the BlockPager is untouched)
        self._mesh, self._tp = serving_mesh(self._leaves)
        if self._mesh is not None and self._has_latent:
            raise NotImplementedError(
                "tensor-parallel serving of latent-attention entries needs "
                "a head-sharded placement of the absorbed queries over a "
                "REPLICATED latent pool (one row serves every head, so "
                "there is no head axis to shard the pool on) and an "
                "\"expert\" axis for held experts: not implemented")
        if self._mesh is not None and (self._has_state or any(
                c.merged_rows for c in spec.entries)):
            raise NotImplementedError(
                "tensor-parallel serving of recurrent-state layers needs a "
                "sharding rule for the state arrays and for merged-row K/V "
                "pools (and an \"expert\" axis for held experts): not "
                "implemented")
        if self._mesh is None:
            # loud refusal beats a deep jit crash: a model sharded over a
            # mesh the engine cannot drive (no "model" axis installed in
            # distributed.env, or a custom axis name) would otherwise die
            # at the first mint with "incompatible devices" and no hint
            for name_t, t in zip(
                    (n for n, _ in model.named_parameters()), self._leaves):
                sh = getattr(t.value(), "sharding", None)
                dset = getattr(sh, "device_set", None)
                if dset is not None and len(dset) > 1:
                    raise NotImplementedError(
                        f"param {name_t!r} is sharded over {len(dset)} "
                        f"devices but the engine found no usable mesh — "
                        f"TP serving requires distributed.env.get_mesh() "
                        f"to carry a \"model\" axis (degree > 1) and the "
                        f"model to be sharded over THAT axis "
                        f"(shard_gpt_tp / shard_llama_tp defaults)")
        self._repl = None
        self._pool_sh = None
        self._kv_pin = False
        if self._mesh is not None:
            self._repl = NamedSharding(self._mesh, P())
            if spec.n_kv_heads % self._tp == 0:
                pool_spec = P(None, None, "model", None)
            elif spec.head_dim % self._tp == 0:
                # GQA fallback: fewer KV heads than chips — shard head_dim
                pool_spec = P(None, None, None, "model")
            else:
                import warnings
                warnings.warn(
                    f"n_kv_heads {spec.n_kv_heads} and head_dim "
                    f"{spec.head_dim} both indivisible by tp={self._tp}; "
                    f"KV pools stay replicated (correct but each chip "
                    f"holds the full pool)", RuntimeWarning)
                pool_spec = P()
            self._pool_sh = NamedSharding(self._mesh, pool_spec)
            # mid-graph scatter/gather constraints only under HEAD sharding,
            # where per-head attention consumes the layout unchanged. In the
            # hd fallback the projections land nkv-and-hd split, so pinning
            # the pool mid-graph forces XLA full-remat copies — there the
            # committed input placement + pinned out_shardings alone keep
            # the storage hd-sharded and the layout stable across calls
            self._kv_pin = pool_spec == P(None, None, "model", None)
            # commit every leaf that does not already live on THIS mesh to
            # a mesh-replicated placement: AOT executables refuse inputs
            # whose shardings drift from the compiled ones, and a single-
            # device leaf next to mesh-sharded pools is exactly that drift
            for t in self._leaves:
                a = t.value()
                sh = getattr(a, "sharding", None)
                if isinstance(sh, NamedSharding) and sh.mesh == self._mesh:
                    continue
                t._data = jax.device_put(a, self._repl)
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(min(block_size, self.max_len))
        self._mbs = -(-self.max_len // self.block_size)
        if kv_blocks is None:
            kv_blocks = self.max_slots * self._mbs + 1
        if kv_blocks < self._mbs + 2:
            raise ValueError(
                f"kv_blocks {kv_blocks} cannot back even one full slot "
                f"({self._mbs} blocks + trash)")
        self.kv_blocks = int(kv_blocks)
        if prefill_chunk is not None and not (
                1 <= int(prefill_chunk) <= self.max_len):
            raise ValueError(f"prefill_chunk must lie in [1, max_len="
                             f"{self.max_len}], got {prefill_chunk}")
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        def _pool(c):
            rows = (self.block_size * c.n_kv_heads,) if c.merged_rows \
                else (self.block_size, c.n_kv_heads)
            lanes = pool_lanes(c.head_dim) if c.kind == "latent" \
                else c.head_dim
            z = jnp.zeros((self.kv_blocks,) + rows + (lanes,),
                          self._cache_dtype)
            return z if self._pool_sh is None \
                else jax.device_put(z, self._pool_sh)
        # a kv entry: a K pool and a V pool; a latent entry: ONE pool of
        # rows; a state entry: its arrays, a row a slot
        self._pools = spec.map_entries(
            lambda c: (_pool(c), _pool(c)) if c.kind == "kv"
            else (_pool(c),) if c.kind == "latent"
            else self._state_rows(c))
        # a prefix hit would skip tokens a recurrent state has to see
        self._pager = BlockPager(self.kv_blocks, self.block_size,
                                 self.max_slots, self._mbs,
                                 prefix_cache=not self._has_state)
        # in-flight chunked prefills: slot -> _PrefillState
        self._prefilling: dict = {}
        self._admit_seq = itertools.count()   # eviction picks youngest
        self._slot_seq = [0] * self.max_slots
        self.preemptions = 0
        # ---- cross-process prefix-cache tier (serving/kvpool.py): parked
        # registered blocks export to the pool, registry-miss admissions
        # fetch + adopt. All host state; zero effect when kv_pool is None.
        if kv_pool is not None and self._has_latent:
            raise NotImplementedError(
                "pool export/adopt speaks [layers, block, kv heads, width] "
                "K and V blocks: a model whose layers cache latent rows "
                "needs a wire codec for its [block, lanes] entries")
        if kv_pool is not None and self._has_state:
            raise NotImplementedError(
                "pool export/adopt moves K/V blocks only: a model with "
                "recurrent-state layers would need its state at the block "
                "boundary exported with them")
        self._kv_pool = kv_pool
        self._pool_gen = 0
        self._exported: set = set()     # digests already in the pool (gen)
        self._adopt_exe = None
        self.pool_exports = 0
        self.pool_export_errors = 0
        self.pool_fetches = 0
        self.pool_fetch_hits = 0
        self.pool_fetch_misses = 0
        self.pool_fetch_s = 0.0
        self.pool_adopted_blocks = 0
        self.pool_adopted_tokens = 0
        if self._kv_pool is not None:
            self._pager.export_enabled = True
            self._pool_gen = int(self._kv_pool.generation())
        if prefill_buckets is None:
            buckets, b = [], 8
            while b < self.max_len:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_len)
        else:
            buckets = [int(b) for b in prefill_buckets]
            if any(b < 1 or b > self.max_len for b in buckets):
                raise ValueError(f"prefill_buckets must lie in "
                                 f"[1, max_len={self.max_len}]: {buckets}")
        self.prefill_buckets = sorted(set(buckets))
        # host-side slot state: cursors/last-token per row; dead rows sit at
        # pos 0 (their decode writes land on the trash block)
        self._pos = np.zeros(self.max_slots, np.int32)
        self._tok = np.zeros(self.max_slots, np.int32)
        self._live = np.zeros(self.max_slots, bool)
        self._slot_req: List[Optional[Request]] = [None] * self.max_slots
        self._slots = SlotAllocator(self.max_slots)
        self._queue = AdmissionQueue(max_queue)
        self._decode_exe = None
        self._decode_attention = None
        self._prefill_attention: dict = {}   # chunk length -> its path
        # ... and, where that path walks the slot's key blocks, the key
        # positions of a trip; what the chunks read of their table rows
        self._prefill_key_block: dict = {}
        self.kv_walked = self.kv_table = 0
        self._decode_geometry = {}
        self._decode_state = None
        # how each executable wrote its new rows into the pools: "kernel"
        # (kernels/pallas/pool_write.py) or "scatter" (XLA's), as traced
        self._kv_write: dict = {}
        self._verify_exe = None
        self._prefill_exes = {}
        # ---- the prepared step (_step_planned). The
        # plan made for the next step while the last one ran, and why one
        # was thrown away since (or could not be made); the last decode's
        # picked tokens, still on the device, which the next decode takes
        # as its own; the COW copies of decode rows made writable but not
        # yet launched; sampling keys drawn for a plan that was discarded
        self._plan: Optional[_Plan] = None
        self._discarded: Optional[str] = None
        self._picked = None
        self._decode_cow: dict = {}
        self._spare_keys: list = []
        self._drawn: Optional[list] = None
        self._note_exe = None
        self._armed: dict = {}             # the watchdog's current window
        # length of the decode executable's token vector: max_slots, plus
        # the routed layers' counts where the model has such layers
        # (what the trace really returned decides: _build_decode)
        from ..incubate.distributed.models.moe.held import HeldExpertsMoE
        self._moe_names = max(
            (l.counter_names for l in model.sublayers(include_self=True)
             if isinstance(l, HeldExpertsMoE)), key=len, default=())
        self._tok_len = self.max_slots + len(self._moe_names)
        # the decode step is told which slots are live (write_end) where a
        # layer keeps per-slot state, or counts the tokens it routes
        self._tells_live = self._has_state or bool(self._moe_names)
        self._slot_dev: dict = {}
        # how each step that ran an executable came by its plan, and why
        # the rebuilt ones lost theirs (stats()["plan"])
        self.plan_counts = {"prepared": 0, "rebuilt": 0, "sync": 0}
        self.plan_causes: dict = {}
        # what each kind of call ("decode", "chunk<sc>", "verify") has been
        # taking ({kind: [samples, running mean]}: of a step's time from
        # its first launch to the end of its wait, an equal share a call),
        # and this step's (kind, seconds); a step far over their sum is
        # sealed as a host/stall record (trace.book; stats()["stalls"])
        self._call_means: dict = {}
        self._step_calls: list = []
        self.stalls = 0
        # cumulative speculation counters (stats() + monitor mirrors)
        self.spec_steps = 0        # verify dispatches
        self.spec_drafted = 0      # tokens proposed by the drafter
        self.spec_accepted = 0     # drafts that agreed with the verifier
        self.spec_emitted = 0      # tokens emitted by spec steps (acc+bonus)
        self._key = jax.random.PRNGKey(int(seed))
        self._greedy_key = jax.random.PRNGKey(0)   # unused by greedy pick
        if self._repl is not None:
            self._key = jax.device_put(self._key, self._repl)
            self._greedy_key = jax.device_put(self._greedy_key, self._repl)
        # serving recompile sentinel (monitor-independent; tests gate on it)
        self.compile_count = 0
        self.decode_steps = 0
        self.tokens_generated = 0
        # running sums of what the decode executable reports of its routed
        # layers (assignments, those to held experts, held experts touched);
        # None until a model with expert layers has decoded
        self.moe_counts = None
        self.engine_id = next(DecodeEngine._ids)
        # ---- guardrail plane (all host state; zero effect until used)
        # injectable clock: deadlines and drain grace read THIS, so tests
        # fast-forward time instead of sleeping
        self._clock = time.time
        self._faults = fault_schedule if fault_schedule is not None \
            else FaultSchedule.from_env()
        if self._faults is not None:
            self._pager.fault_schedule = self._faults
        if hang_s is None:
            try:
                hang_s = float(os.environ.get(HANG_ENV, "0") or 0)
            except ValueError:
                hang_s = 0.0
        self._watchdog = DispatchWatchdog(hang_s, self._on_hang) \
            if hang_s and hang_s > 0 else None
        # terminal transitions that happened OUTSIDE a step (cancel(), a
        # failed engine's terminalizations): the next step() returns them,
        # so pollers of step()'s return see every terminal exactly once
        self._terminal_buf: List[Request] = []
        # non-terminal requests carrying a deadline: the expiry sweep is
        # O(queue + slots) per step, so it early-outs when this is empty
        # (the common no-deadline workload pays one set check per step)
        self._deadline_reqs: set = set()
        # requeue idempotency: caller-supplied request ids this engine has
        # admitted, live plus a bounded window of terminal ones. A router
        # retrying a submit it isn't sure landed gets the EXISTING Request
        # back — one id can never generate twice on one engine.
        self._by_id: dict = {}
        self._done_ids: "OrderedDict" = OrderedDict()
        self._draining = False
        self._drain_t0: Optional[float] = None
        self._drain_deadline: Optional[float] = None
        self._drain_reported = False
        self._pw = None                    # PreemptionWatcher, if wired
        self._pw_grace_s: Optional[float] = None
        # cumulative guardrail counters (stats() + monitor mirrors)
        self.expired = 0
        self.cancelled = 0
        self.drains = 0
        self.nan_logits = 0
        # ---- why the queue waits. Two clocks run while the head of a
        # non-empty queue finds no free slot / no KV blocks; a request's
        # own waits are their advance between its enqueue and its
        # admission, so a step books them in O(1) whatever the queue holds
        self._slot_wait_clock = 0.0
        self._block_wait_clock = 0.0
        self._wait_cause: Optional[str] = None   # booked since _wait_mark
        self._wait_mark = 0.0
        # cumulative, for an operator's dashboard (stats())
        self.queue_waits = 0
        self.queue_wait_s_sum = 0.0
        self.block_waits = 0
        self.block_wait_s_sum = 0.0
        self.page_rejects = 0
        mon = _monitor._active
        if mon is not None:
            mon.serve_engine(self.max_slots, self.max_len,
                             self.prefill_buckets, quantize,
                             engine_id=self.engine_id, paged=True,
                             block_size=self.block_size,
                             kv_blocks=self.kv_blocks,
                             prefill_chunk=self.prefill_chunk, tp=self._tp,
                             drafter=getattr(drafter, "name", None)
                             if drafter is not None else None)

    # ------------------------------------------------------------- tracing

    def _traced(self, leaf_arrays, body):
        """Run ``body`` with every model param/buffer rebound to the traced
        input arrays (the _generate_with_cache idiom): the executables own
        their weights as ARGUMENTS, never as baked-in constants."""
        from ..core import dispatch
        ctx = dispatch.TraceContext()
        saved = [t._data for t in self._leaves]
        dispatch.push_trace(ctx)
        try:
            for t, a in zip(self._leaves, leaf_arrays):
                t._data = a
            return body()
        finally:
            dispatch.pop_trace()
            ctx.restore()
            for t, d in zip(self._leaves, saved):
                t._data = d

    def _head(self, hidden):
        # shared with the eager compiled loop — the parity contract
        return _lm_head_logits(hidden, self.spec.head_weight,
                               self.spec.head_transpose)

    def _pick(self, logits, key):
        return _pick_token(logits, key, self._do_sample, self._temperature,
                           self._top_k)

    def _leaf_values(self):
        return tuple(t.value() for t in self._leaves)

    def _dev(self, x):
        """Host data -> device argument. Under a mesh, commit it REPLICATED
        so the SPMD executables' compiled input shardings always match (the
        block table, cursors, token ids and COW index pairs are rank-
        replicated data by design — the pager never learns about the mesh).
        """
        a = jnp.asarray(x)
        return a if self._repl is None else jax.device_put(a, self._repl)

    def _next_key(self):
        """The next call's sampling key. Keys drawn for a plan that was
        thrown away come first, so a seed gives the same stream whichever
        way its steps were built."""
        if not self._do_sample:
            return self._greedy_key
        if self._spare_keys:
            sub = self._spare_keys.pop(0)
        else:
            self._key, sub = jax.random.split(self._key)
            if self._repl is not None:
                self._key = jax.device_put(self._key, self._repl)
                sub = jax.device_put(sub, self._repl)
        if self._drawn is not None:
            self._drawn.append(sub)
        return sub

    def _slot_index(self, slot: int):
        """``slot`` as a device scalar (made once a slot)."""
        dev = self._slot_dev.get(slot)
        if dev is None:
            dev = self._slot_dev[slot] = self._dev(np.int32(slot))
        return dev

    def _host_tok(self):
        """The host's last token of every slot, as long as the decode
        executable's token vector (a fresh array: uploads are not waited
        for, so what is uploaded is never written again)."""
        tok = np.zeros(self._tok_len, np.int32)
        tok[:self.max_slots] = self._tok
        return tok

    def _compile_in_eval(self, fn, args, out_shardings=None):
        return self._lower_in_eval(fn, args, out_shardings).compile()

    def _lower_in_eval(self, fn, args, out_shardings=None):
        """Trace for AOT compilation with every layer in eval mode (serving
        semantics: dropout off), then restore each layer's OWN flag — an
        engine must not flip a training model's mode as a side effect.
        Under a mesh the paged-pool sharding context is installed for the
        duration of the trace (head-sharded, ``_paged_kv_write/_gather``
        pin the scatter/gather shard-local on the head axis; sharded at
        all, the decode step keeps the gather path) and ``out_shardings``
        pins the donated pools back to their input placement — without the
        pin, XLA's propagation could hand back differently-laid pools and
        the NEXT call's input shardings would no longer match the compiled
        ones."""
        layers = self.model.sublayers(include_self=True)
        saved = [(l, l.training) for l in layers]
        for l in layers:
            l.training = False
        prev_ctx = set_paged_kv_sharding(self._pool_sh, self._kv_pin) \
            if self._mesh is not None else None
        try:
            kw = dict(donate_argnums=(1,))
            if out_shardings is not None:
                kw["out_shardings"] = out_shardings
            return jax.jit(fn, **kw).lower(*args)
        finally:
            if self._mesh is not None:
                set_paged_kv_sharding(*prev_ctx)
            for l, f in saved:
                l.training = f

    def _pool_out_shardings(self):
        """out_shardings pytree for (new_pools, picked_token, logits_ok)
        returns — pools pinned to their (possibly head-sharded) input
        placement, the token and the finite-logits flag replicated. None
        off the mesh (single-chip: let jax infer)."""
        if self._mesh is None:
            return None
        return ([(self._pool_sh, self._pool_sh)
                 for _ in range(self.spec.num_layers)], self._repl,
                self._repl)

    def _minted(self, kind: str, bucket, compile_s: float, exe=None,
                tokens=None):
        self.compile_count += 1
        mon = _monitor._active
        if mon is not None:
            mon.serve_compiled(
                kind, bucket, compile_s, self.compile_count,
                engine_id=self.engine_id, compiled=exe, tokens=tokens,
                analytic_flops=(2.0 * self._n_params * tokens
                                if tokens else None),
                devices=self._tp)

    # ------------------------------------------------ per-layer caches

    def _state_rows(self, layer):
        """A state layer's arrays, one row a slot: ``[max_slots, *shape]``.
        A row's content only matters between its tenant's first chunk,
        which starts it from zero, and that tenant's last token."""
        return tuple(jnp.zeros((self.max_slots,) + shape, jnp.dtype(dtype))
                     for shape, dtype in layer.arrays)

    def _layer_caches(self, pools, table, slot=None):
        """What each layer's cached forward is handed, entry by entry: a
        kv entry its pools and ``table`` (the rows of the call's slots), a
        state entry its arrays' rows, ``slot``'s alone for a one-slot
        call."""
        def hand(entry, cache):
            if entry.kind in _PAGED:
                return tuple(cache) + (table,)
            if slot is None:
                return tuple(cache)
            return tuple(jax.lax.dynamic_slice_in_dim(a, slot, 1, 0)
                         for a in cache)
        return self.spec.map_entries(hand, pools)

    def _layer_results(self, pools, new, slot=None):
        """The caches to keep after a call: what the backbone returned, a
        one-slot call's state rows written back at ``slot``."""
        def keep(entry, cache, n):
            if entry.kind in _PAGED or slot is None:
                return tuple(n)
            return tuple(jax.lax.dynamic_update_slice_in_dim(
                a, r.astype(a.dtype), slot, 0) for a, r in zip(cache, n))
        return self.spec.map_entries(keep, pools, new)

    def _backbone(self, ids, caches, **kw):
        """The cached forward, with whatever its routed layers count of
        the call (int32 [3], or None for a model without any)."""
        from ..incubate.distributed.models.moe.held import collect_counters
        with collect_counters() as counted:
            hidden, new = self.spec.backbone(Tensor(ids), kv_caches=caches,
                                             **kw)
        return hidden, new, counted.total()

    # --------------------------------------------------------- executables

    def _apply_cow(self, pools, src, dst):
        """Fold the pager's pending copy-on-write block copies into the
        executable: ``pools[l][dst[i]] = pools[l][src[i]]`` before anything
        reads or writes. Padded entries are (0, 0) trash-to-trash no-ops,
        so the shape is always [max_slots] and COW never retraces. On one
        chip one kernel copies the real pairs' blocks of every pool
        (``pool_write.copy_blocks``: a padded pair costs a predicate);
        under a mesh and off the chip a gather and a scatter of every pair.
        State layers have no blocks to copy."""
        flat = []
        self.spec.map_entries(
            lambda entry, c: flat.extend(c) if entry.kind in _PAGED else None,
            pools)
        mode = pool_write.copy_mode(flat[0]) \
            if flat and self._mesh is None else None
        if mode is None:
            return self.spec.map_entries(
                lambda entry, c: tuple(
                    p.at[dst].set(jnp.take(p, src, axis=0)) for p in c)
                if entry.kind in _PAGED else c, pools)
        done = iter(pool_write.copy_blocks(flat, src, dst,
                                           interpret=mode == "interpret"))
        return self.spec.map_entries(
            lambda entry, c: tuple(next(done) for _ in c)
            if entry.kind in _PAGED else c, pools)

    def _sample(self, hidden_last, key, moe=None):
        """LM head + pick over ``hidden_last [B, H]``: (token ids int32 [B],
        per-row finite-logits flag). With ``moe`` (the routed layers'
        counts) the ids carry them as a tail, so one fetch brings both."""
        with jax.named_scope("lm_head_sample"):
            logits = self._head(hidden_last)
            nxt = self._pick(logits, key).astype(jnp.int32)
            # per-slot finite-logits flag: data, not shape - NaN detection
            # never retraces, and a clean step pays one row-reduce fused
            # into the head matmul's epilogue
            ok = jnp.all(jnp.isfinite(logits), axis=-1)
        if moe is not None:
            nxt = jnp.concatenate([nxt, moe])
        return nxt, ok

    def _build_decode(self):
        # a model with state layers (or routed layers, which count their
        # tokens) is told which slots are live: write_end = pos + 1 for
        # them, pos for the rest, whose state (and K/V) the step must leave
        # alone.
        # ``tok`` is as long as the step's own picked tokens (with the
        # routed layers' counts behind them, where there are any): the
        # next step is handed this step's output as it lies on the
        # device, and the host never has to read it first
        def fn(leaves, pools, table, tok, pos, cow_src, cow_dst, key, *end):
            def body():
                pools2 = self._apply_cow(pools, cow_src, cow_dst)
                hidden, new, moe = self._backbone(
                    tok[:self.max_slots, None],
                    self._layer_caches(pools2, table),
                    start_pos=pos, **dict(zip(("write_end",), end)))
                nxt, ok = self._sample(hidden.value()[:, -1], key, moe)
                return self._layer_results(pools2, new), nxt, ok
            return self._traced(leaves, body)

        pad = self._dev(jnp.zeros(self.max_slots, jnp.int32))
        args = (self._leaf_values(), self._pools,
                self._dev(self._pager.tables),
                self._dev(self._host_tok()), self._dev(self._pos), pad,
                pad, self._greedy_key)
        if self._tells_live:
            args += (self._dev(self._pos),)
        t0 = time.time()
        from ..kernels.pallas import paged_decode
        from ..kernels.pallas.util import state_kernels_traced
        traced = len(attention_kernels_traced())
        state_traced = len(state_kernels_traced())
        writes = len(pool_writes_traced())
        low = self._lower_in_eval(fn, args, self._pool_out_shardings())
        n_out = low.out_info[1].shape[0]
        if n_out != self._tok_len:
            # counts from a layer __init__ did not know of: the step's
            # output is the next step's input, so trace at its length
            self._tok_len = n_out
            args = args[:3] + (self._dev(self._host_tok()),) + args[4:]
            low = self._lower_in_eval(fn, args, self._pool_out_shardings())
        exe = low.compile()
        self._build_note(args[3])
        self._decode_exe = exe
        # which attention the trace took (the model chose from its input:
        # models/gpt.py::_paged_decode_attend); a silent fallback on the
        # chip would otherwise look like "no gain"
        self._decode_attention = self._attention_path(traced)
        self._kv_write["decode"] = self._write_path(writes)
        # and with which walk of the table the kernel was traced
        # (`kv_chunk_pages`, `kv_page_bytes`: it sizes a chunk from its input)
        self._decode_geometry = paged_decode.kernel_geometry() \
            if self._decode_attention != "gather" else {}
        # and which step a model with state entries took for them: the
        # kernel's name, or "scan" for the ``jax.numpy`` recurrence
        if self._has_state:
            self._decode_state = "+".join(sorted(set(
                state_kernels_traced(state_traced)))) or "scan"
        # the decode step advances one token per SLOT per call
        self._minted("decode", None, time.time() - t0, exe=exe,
                     tokens=self.max_slots)
        return exe

    @staticmethod
    def _attention_path(mark: int) -> str:
        """Which attention kernels were traced since ``mark`` (a length of
        ``kernels/pallas/util.py::attention_kernels_traced()``):
        ``"paged_kernel"`` (the K/V walk), ``"mla_decode"`` (its latent
        geometry), or ``"gather"`` for the view."""
        return "+".join(sorted(set(attention_kernels_traced(mark)))) \
            or "gather"

    @staticmethod
    def _write_path(mark: int):
        """How the rows were written since ``mark`` (a length of
        ``pool_writes_traced()``): ``"kernel"``, ``"scatter"``, both
        joined by ``+``, or None for no write."""
        return "+".join(sorted(set(pool_writes_traced(mark)))) or None

    def _build_note(self, tok):
        """The one helper program of the prepared step: write a chunk's
        first token, still on the device, into the decode step's token
        vector at its slot. (Its name keeps it apart from the engine's two
        executables, which a profile lists as ``jit_fn``.)"""
        def note_first_token(tok, first, slot):
            return tok.at[slot].set(first)

        kw = {} if self._repl is None else {"out_shardings": self._repl}
        zero = self._dev(np.int32(0))
        self._note_exe = jax.jit(note_first_token, **kw).lower(
            tok, zero, zero).compile()

    def _build_chunk(self, sc: int):
        """Paged prefill chunk: run ``sc`` prompt tokens of ONE slot through
        the backbone at absolute start position ``p0``, reading/writing K/V
        through the slot's block-table row (any already-cached prefix —
        earlier chunks or shared blocks — is attended via the table).
        ``end`` is the absolute end of VALID tokens in this call: the write
        path trashes the padded tail, and the returned token is picked from
        the true last position (only the final chunk's pick is used)."""
        mbs = self._mbs

        def fn(leaves, pools, table, ids, slot, p0, end, cow_src, cow_dst,
               key):
            def body():
                pools2 = self._apply_cow(pools, cow_src, cow_dst)
                row = jax.lax.dynamic_slice(table, (slot, jnp.int32(0)),
                                            (1, mbs))
                hidden, new, _ = self._backbone(
                    ids, self._layer_caches(pools2, row, slot),
                    start_pos=p0, write_end=end)
                with jax.named_scope("lm_head_sample"):
                    h_last = jax.lax.dynamic_slice_in_dim(
                        hidden.value(), end - p0 - 1, 1, axis=1)[:, 0]
                    logits = self._head(h_last)
                    tok0 = self._pick(logits, key).astype(jnp.int32)
                    ok = jnp.all(jnp.isfinite(logits))
                return self._layer_results(pools2, new, slot), tok0[0], ok
            return self._traced(leaves, body)

        pad = self._dev(jnp.zeros(self.max_slots, jnp.int32))
        args = (self._leaf_values(), self._pools,
                self._dev(self._pager.tables),
                self._dev(jnp.zeros((1, sc), jnp.int32)),
                self._dev(jnp.int32(0)), self._dev(jnp.int32(0)),
                self._dev(jnp.int32(1)), pad, pad, self._greedy_key)
        t0 = time.time()
        traced = len(attention_kernels_traced())
        writes = len(pool_writes_traced())
        exe = self._compile_in_eval(fn, args,
                                    out_shardings=self._pool_out_shardings())
        self._prefill_exes[sc] = exe
        self._kv_write[sc] = self._write_path(writes)
        self._prefill_attention[sc] = self._attention_path(traced)
        if self._prefill_attention[sc] == "key_walk":
            from ..models.hybrid import walk_geometry
            self._prefill_key_block[sc] = walk_geometry()["key_block"]
        self._minted("prefill", sc, time.time() - t0, exe=exe, tokens=sc)
        return exe

    def _build_verify(self):
        """Speculative verify: the chunk machinery verbatim — ``[1, vw]``
        ids through ONE slot's block-table row at absolute position ``p0``,
        write path trashed past ``end`` — except the pick happens at EVERY
        position instead of just the last. Position i's argmax is the
        model's next token after ids[i], which is exactly the agreement
        test the accept loop needs, and position a's argmax doubles as the
        bonus token. Minted once per engine: drafts ride as ids DATA, so
        no drafter can change this shape."""
        spec = self.spec
        mbs = self._mbs
        vw = self._spec_width

        def fn(leaves, pools, table, ids, slot, p0, end, cow_src, cow_dst,
               key):
            def body():
                pools2 = self._apply_cow(pools, cow_src, cow_dst)
                row = jax.lax.dynamic_slice(table, (slot, jnp.int32(0)),
                                            (1, mbs))
                caches = [(pk, pv, row) for pk, pv in pools2]
                hidden, new_pools = spec.backbone(
                    Tensor(ids), kv_caches=caches, start_pos=p0,
                    write_end=end)
                logits = self._head(hidden.value()[0])        # [vw, V]
                picked = self._pick(logits, key).astype(jnp.int32)
                # one flag over every verified position: a NaN anywhere in
                # the window poisons the accept test, so the whole dispatch
                # is disqualified rather than attributed per position
                ok = jnp.all(jnp.isfinite(logits))
                return new_pools, picked, ok
            return self._traced(leaves, body)

        pad = self._dev(jnp.zeros(self.max_slots, jnp.int32))
        args = (self._leaf_values(), self._pools,
                self._dev(self._pager.tables),
                self._dev(jnp.zeros((1, vw), jnp.int32)),
                self._dev(jnp.int32(0)), self._dev(jnp.int32(0)),
                self._dev(jnp.int32(1)), pad, pad, self._greedy_key)
        t0 = time.time()
        writes = len(pool_writes_traced())
        exe = self._compile_in_eval(fn, args,
                                    out_shardings=self._pool_out_shardings())
        self._verify_exe = exe
        self._kv_write["verify"] = self._write_path(writes)
        self._minted("verify", vw, time.time() - t0, exe=exe, tokens=vw)
        return exe

    def _pool_geom(self) -> list:
        """KV geometry fingerprint carried in every pool entry's meta: a
        fetched block only adopts when the exporter's geometry matches
        ours exactly (a mismatch is a MISS — heterogeneous engines sharing
        a pool degrade to per-process caching, they never corrupt)."""
        return [int(self.spec.num_layers), int(self.block_size),
                int(self.spec.n_kv_heads), int(self.spec.head_dim)]

    def _build_adopt(self):
        """Pool-block splice: write one physical block row of EVERY
        layer's K/V pool from host data. The row index and the bytes are
        arguments — data, not shape — so the executable mints ONCE and
        adoption never recompiles; pools are donated and pinned back to
        their input sharding exactly like the decode step's."""
        L = self.spec.num_layers

        def fn(idx, pools, kd, vd):
            return [(pk.at[idx].set(kd[l].astype(pk.dtype)),
                     pv.at[idx].set(vd[l].astype(pv.dtype)))
                    for l, (pk, pv) in enumerate(pools)]

        zero = self._dev(jnp.zeros(
            (L, self.block_size, self.spec.n_kv_heads, self.spec.head_dim),
            self._cache_dtype))
        args = (self._dev(jnp.int32(TRASH_BLOCK)), self._pools, zero, zero)
        out_sh = None if self._mesh is None else \
            [(self._pool_sh, self._pool_sh) for _ in range(L)]
        t0 = time.time()
        exe = self._compile_in_eval(fn, args, out_shardings=out_sh)
        self._adopt_exe = exe
        self._minted("adopt", None, time.time() - t0, exe=exe)
        return exe

    # ----------------------------------------------------------- requests

    def _bucket_for(self, n: int) -> Optional[int]:
        for b in self.prefill_buckets:
            if b >= n:
                return b
        return None

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None, request_id=None,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Validate + enqueue one request. A malformed request comes back
        ``failed`` with ``error`` set and is never admitted — the live
        batch cannot be poisoned by one bad input. A well-formed request
        hitting a FULL admission queue comes back ``rejected_overload``
        (saturation is the caller's signal to back off, not the engine's
        license to grow host memory without bound); one arriving while the
        engine drains comes back ``rejected_draining`` (the door is
        closed, resubmit to the replacement process).

        ``ttft_deadline_s`` bounds submit -> first token; ``deadline_s``
        bounds the whole request. Both are enforced at step boundaries —
        expiry releases the slot and KV blocks exactly once and the
        request ends ``expired``.

        A caller-supplied ``request_id`` makes submission IDEMPOTENT on
        this engine: a duplicate id returns the existing Request (live,
        or terminal within the dedup window) instead of admitting twice —
        the router's requeue/retry contract depends on one id never
        producing two token streams. Door bounces (``rejected_draining``
        / ``rejected_overload``) are not remembered: a bounced id must
        stay resubmittable."""
        if request_id is not None:
            dup = self._by_id.get(request_id)
            if dup is None:
                dup = self._done_ids.get(request_id)
            if dup is not None:
                return dup
        try:
            req = Request(prompt, max_new_tokens=max_new_tokens,
                          eos_token_id=eos_token_id, request_id=request_id,
                          ttft_deadline_s=ttft_deadline_s,
                          deadline_s=deadline_s)
        except (TypeError, ValueError, OverflowError) as e:
            # the fallback Request must not re-raise: pin every field to a
            # known-safe value (the original bad ones live in the message)
            req = Request([], max_new_tokens=1, request_id=request_id)
            self._reject(req, f"invalid request: {e}")
            return req
        # one trace per request (trace id = request id; head-sampled at the
        # door where a sink is on); phases open and close across step()
        # iterations so a TTFT decomposes as queue + prefill (+ requeue
        # episodes) with no gaps
        req._trace = _trace.start_trace(
            "request", req.id, "request", current=False, root="total",
            request=req.id, engine=self.engine_id, prompt=len(req.prompt),
            max_new=req.max_new_tokens)
        n = len(req.prompt)
        if n == 0:
            self._reject(req, "empty prompt")
        elif req.max_new_tokens < 1:
            self._reject(req, f"max_new_tokens must be >= 1, "
                              f"got {req.max_new_tokens}")
        elif n >= self.max_len:
            self._reject(req, f"prompt length {n} >= engine max_len "
                              f"{self.max_len} (no room to decode)")
        elif n + req.max_new_tokens > self.max_len:
            self._reject(req, f"prompt {n} + max_new_tokens "
                              f"{req.max_new_tokens} exceeds engine "
                              f"max_len {self.max_len}")
        elif self._pager.blocks_for(
                n + req.max_new_tokens) > self._pager.usable_blocks:
            self._reject(req, f"request needs "
                              f"{self._pager.blocks_for(n + req.max_new_tokens)} "
                              f"KV blocks, pool holds "
                              f"{self._pager.usable_blocks}")
        elif (self.prefill_chunk is None
              and self._bucket_for(n) is None):
            self._reject(req, f"prompt length {n} exceeds the largest "
                              f"prefill bucket "
                              f"({self.prefill_buckets[-1]})")
        elif self._draining:
            req.status, req.error = "rejected_draining", \
                "engine is draining (shutdown in progress)"
            req.t_done = time.time()
            mon = _monitor._active
            if mon is not None:
                mon.serve_request(queued=False, error=req.error,
                                  draining=True)
            req._trace.end(status="rejected_draining", error=req.error)
        elif not self._queue.push(req):
            req.status, req.error = "rejected_overload", \
                f"admission queue full ({self._queue.max_queue})"
            req.t_done = time.time()
            mon = _monitor._active
            if mon is not None:
                mon.serve_request(queued=False, error=req.error,
                                  overload=True)
            req._trace.end(status="rejected_overload", error=req.error)
        else:
            if req.ttft_deadline_s is not None or req.deadline_s is not None:
                self._deadline_reqs.add(req)
            if request_id is not None:
                self._by_id[req.id] = req
            mon = _monitor._active
            if mon is not None:
                mon.serve_request(queued=True)
            self._open_queue_phase(req)
        return req

    def _reject(self, req: Request, why: str):
        req.status, req.error, req.t_done = "failed", why, time.time()
        mon = _monitor._active
        if mon is not None:
            mon.serve_request(queued=False, error=why)
        if req._trace is not None:     # the malformed-request stand-in has none
            req._trace.end(status="failed", error=why)

    # ------------------------------------------------------- queue waits

    def _book_wait(self, now: float, cause: Optional[str]):
        """Book the time since the last look to what the queue was waiting
        for then, and note what it waits for from now on (``"slot"``,
        ``"blocks"`` or nothing)."""
        if self._wait_cause == "slot":
            self._slot_wait_clock += now - self._wait_mark
        elif self._wait_cause == "blocks":
            self._block_wait_clock += now - self._wait_mark
        self._wait_cause, self._wait_mark = cause, now

    def _open_queue_phase(self, req: Request, **attrs):
        """``req`` entered the queue (at submit, or again after a
        preemption): open its ``queue`` span and note where the two wait
        clocks stand."""
        now = time.perf_counter()
        self._book_wait(now, self._wait_cause)
        req._wait0 = (self._slot_wait_clock, self._block_wait_clock)
        req._page_rejects = 0
        req._trace_phase("queue", t0=now, **attrs)

    def _close_queue_phase(self, req: Request, slot: int) -> float:
        """``req`` got ``slot``: fill in its ``queue`` span with what it
        waited for (whichever cause held it longest; ``"none"`` = only for
        the step in flight). Returns the instant, at which the caller opens
        the next phase: that closes the span."""
        now = time.perf_counter()
        self._book_wait(now, self._wait_cause)
        slot_s = self._slot_wait_clock - req._wait0[0]
        block_s = self._block_wait_clock - req._wait0[1]
        cause = "none" if slot_s <= 0.0 and block_s <= 0.0 \
            else "blocks" if block_s >= slot_s else "slot"
        wait_s = now - req._phase.t0
        self.queue_waits += 1
        self.queue_wait_s_sum += wait_s
        if block_s > 0.0:
            self.block_waits += 1
            self.block_wait_s_sum += block_s
        req._phase.set(slot=slot, cause=cause, slot_wait_s=round(slot_s, 6),
                       block_wait_s=round(block_s, 6),
                       page_rejects=req._page_rejects)
        mon = _monitor._active
        if mon is not None:
            # measured from the LAST enqueue (a preemption re-queue opens a
            # new span), so the histogram and the span agree by construction
            mon.serve_queue_wait(wait_s)
        return now

    # ---------------------------------------------------------- scheduling

    @property
    def live_count(self) -> int:
        return int(self._live.sum())

    @property
    def active_count(self) -> int:
        """Admitted concurrent requests: decoding + mid-prefill."""
        return self.live_count + len(self._prefilling)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def step(self) -> List[Request]:
        """ONE iteration of continuous batching: enforce deadlines and
        drain state, fold queued prompts into free slots, advance every
        in-flight chunked prefill by at most ``prefill_chunk`` tokens,
        then decode every live slot one token. Returns every request that
        reached a TERMINAL status since the last step (done / failed /
        expired / cancelled / rejected_draining — one list, one contract).

        The engine does the host's share of a step while the device runs
        the step before it: a step LAUNCHES the
        chunk and decode calls prepared under the last one, PREPARES the
        next step's while they run, then COLLECTS (one wait, one
        read-back) and books the tokens. Every call a step launches has
        ended when ``step()`` returns, and a token is handed over in the
        step that made it (``_step_planned``, which also says what a
        drafter changes).
        """
        calls = self._step_calls = []     # (a step that raised left its own)
        with _trace.span("engine/step") as whole:
            finished = self._step(whole)
        if calls and _trace.book(whole, calls, self._call_means,
                                 engine=self.engine_id) is not None:
            self.stalls += 1
        mon = _monitor._active
        if mon is not None:
            # goodput bracket: the whole scheduler iteration; the executable
            # calls inside classify as productive/compile, the remainder is
            # engine host overhead — the serving timeline stays gap-free
            mon.serve_sched(whole.t0, whole.t1)
        return finished

    def _step(self, whole) -> List[Request]:
        """The phases of one iteration, each one span; together they tile
        ``engine/step`` (``whole``). After the sweep the engine launches,
        prepares and collects (``_step_planned``)."""
        finished: List[Request] = []
        with _trace.span("engine/sweep"):
            if self._terminal_buf:
                # cancel()/engine-failure terminalizations since the last
                # step
                finished.extend(self._terminal_buf)
                self._terminal_buf.clear()
            # SIGTERM wiring: the watcher recorded a signal -> begin
            # draining at THIS step boundary (never mid-executable-call)
            if not self._draining and self._pw is not None \
                    and self._pw.requested():
                self.begin_drain(self._pw_grace_s)
            now = self._clock()
            self._expire_sweep(now, finished)
            if self._draining:
                self._drain_step(now, finished)
        self._step_planned(finished, whole)
        if self._kv_pool is not None:
            # serialize freshly parked registered blocks OUT to the pool at
            # the end of the iteration — never inside the admission/decode
            # hot path — bounded per step so exports cannot stall decode
            with _trace.span("engine/pool_export"):
                self._drain_pool_exports()
            mon = _monitor._active
            if mon is not None:
                mon.serve_pool(self.pool_stats(), engine_id=self.engine_id)
        if self._draining and self.drained and not self._drain_reported:
            self._drain_reported = True
            self.drains += 1
            mon = _monitor._active
            if mon is not None:
                mon.serve_drain_end(self._clock() - (self._drain_t0 or now))
        return finished

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain the work queue: step until queue and slots are empty.
        ``max_steps`` is a hard budget — exactly that many scheduler
        iterations run before the undrained engine raises."""
        out: List[Request] = []
        steps = 0
        while self._queue or self._live.any() or self._prefilling \
                or self._terminal_buf:
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"run() exceeded max_steps={max_steps} with "
                    f"{len(self._queue)} queued / {self.live_count} live")
            out.extend(self.step())
            steps += 1
        return out

    def _admit_phase(self, finished: List[Request]):
        if not self._draining:
            with _trace.span("engine/admit") as adm:
                admitted, refused = self._admit_queued(finished)
                adm.set(admitted=admitted, refused=refused)

    def _admit_queued(self, finished: List[Request]):
        """Fold queued prompts into free slots (the admission half of
        step()). The "admit" fault site counts ATTEMPTS — a blocked
        head-of-line request retrying every step keeps counting — and an
        injected raise fails just that request, cleanly. Returns (requests
        admitted, requests refused for want of KV blocks)."""
        admitted = refused = 0
        while self._queue and self._slots.n_free:
            head = self._queue.peek()
            if self._faults is not None:
                try:
                    self._faults.fire("admit")
                except InjectedFault as e:
                    self._queue.pop()
                    self._terminalize(head, "failed", str(e), finished)
                    continue
            if not self._try_admit_paged(head):
                refused = 1
                break          # head-of-line waits for blocks, FIFO kept
            self._queue.pop()
            admitted += 1
        # whoever is still queued waits, from here to the next look, for
        # blocks (the head was refused) or for a slot (none was free)
        self._book_wait(time.perf_counter(), None if not self._queue
                        else "blocks" if refused else "slot")
        return admitted, refused

    # ----------------------------------------------------------- guardrails

    def _unforeseen(self, why: str):
        """Something happened that a plan made beforehand could not know
        of (``why``: stop, nan, cancel, expire, drain, preempt, blocks,
        fault): the plan, if there is one, is thrown away, and the next
        step is built from the state as it then is, before its launch.
        Whatever the plan's making changed is real state that stays true
        (requests admitted, blocks made writable, with their COW copies
        still pending): only the device arguments derived from it go, and
        the sampling keys drawn for them, which the rebuilt step reuses."""
        plan, self._plan = self._plan, None
        if plan is not None:
            self._spare_keys[:0] = plan.keys
            self._discarded = self._discarded or why

    def _release_slot_state(self, slot: int, why: Optional[str]):
        """Return ``slot`` to the allocator and zero its host row — the ONE
        release path shared by finish / preempt / expire / cancel / engine
        failure, so a request's blocks can never be released twice (the
        pager decrefs exactly once; registered blocks re-park in the
        prefix LRU with refcounts intact). ``why`` says what a prepared
        step could not have known (``_unforeseen``); None for a request
        that stopped at the length it asked for."""
        if why is not None:
            self._unforeseen(why)
        self._decode_cow.pop(slot, None)
        self._prefilling.pop(slot, None)
        self._live[slot] = False
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._slot_req[slot] = None
        self._pager.release_slot(slot)
        self._slots.release(slot)

    def _nan_logits(self, req: Request, where: str):
        """Account one non-finite-logits trip (the caller releases the slot
        and terminalizes the request as ``failed``): always-on engine
        counter plus the monitor's ``serve/nan_logits`` mirror, trace-linked
        to the victim request."""
        self.nan_logits += 1
        mon = _monitor._active
        if mon is not None:
            mon.serve_nan_logits(where, trace_id=req._trace.trace_id)

    def _retire_id(self, req: Request):
        """Dedup bookkeeping at terminalization: a tracked id moves from
        the live map to the bounded terminal window — EXCEPT a drain
        bounce (``rejected_draining``), which generated nothing and must
        stay resubmittable so the router can park-and-requeue it."""
        if self._by_id.pop(req.id, None) is None:
            return
        if req.status == "rejected_draining":
            return
        self._done_ids[req.id] = req
        while len(self._done_ids) > DEDUP_WINDOW:
            self._done_ids.popitem(last=False)

    def _terminalize(self, req: Request, status: str, why: str,
                     finished: Optional[List[Request]], where: str = None):
        """Move ``req`` (queue position / slot already released by the
        caller) to a terminal status, closing its trace and telemetry.
        ``finished=None`` buffers it for the next step() return instead
        (transitions made between steps, e.g. cancel())."""
        assert status in TERMINAL_STATUSES and not req.finished
        self._deadline_reqs.discard(req)
        req.status, req.error = status, why
        self._retire_id(req)
        req.slot = None
        req.t_done = time.time()
        (self._terminal_buf if finished is None else finished).append(req)
        mon = _monitor._active
        trace_id = req._trace.trace_id
        if mon is not None:
            # dedicated counters, not serve/completions — the summary's
            # "completed" stays stop-condition completions, and requests
            # still add up: completed + rejected + expired + cancelled
            if status == "expired":
                mon.serve_expired(where or "?", preemptions=req.preemptions,
                                  tokens=len(req.tokens),
                                  trace_id=trace_id)
            elif status == "cancelled":
                mon.serve_cancelled(where or "?", trace_id=trace_id)
            elif status == "rejected_draining":
                mon.serve_request(queued=False, error=why, draining=True)
        mono = time.perf_counter()
        req._trace_phase(None, t0=mono)
        req._trace.end(t1=mono, status=status, error=why,
                       tokens=len(req.tokens), preemptions=req.preemptions)
        if status == "expired":
            self.expired += 1
        elif status == "cancelled":
            self.cancelled += 1

    def _expire_sweep(self, now: float, finished: List[Request]):
        """Enforce deadlines at the step boundary, across every state a
        request can be in: queued (a preempted/requeued request included —
        its blocks were already released at preemption), mid-chunked-
        prefill, and decoding. Slot + pager blocks release exactly once.
        Early-outs when no live request carries a deadline — the common
        workload pays one set check, not an O(queue+slots) scan."""
        if not self._deadline_reqs:
            return
        for req in [r for r in self._queue if r.deadline_exceeded(now)]:
            which = req.deadline_exceeded(now)
            if self._queue.remove(req):
                self._terminalize(req, "expired",
                                  f"{which} deadline exceeded in queue",
                                  finished, where="queue")
        for slot in [s for s, st in list(self._prefilling.items())
                     if st.req.deadline_exceeded(now)]:
            st = self._prefilling[slot]
            which = st.req.deadline_exceeded(now)
            self._release_slot_state(slot, "expire")
            self._terminalize(st.req, "expired",
                              f"{which} deadline exceeded mid-prefill "
                              f"({st.done}/{st.n} tokens cached)",
                              finished, where="prefill")
        for slot in range(self.max_slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            which = req.deadline_exceeded(now)
            if which is not None:
                self._release_slot_state(slot, "expire")
                self._terminalize(req, "expired",
                                  f"{which} deadline exceeded mid-decode "
                                  f"({len(req.tokens)} tokens out)",
                                  finished, where="decode")

    def cancel(self, req) -> bool:
        """Cancel one request wherever it is — queued, mid-prefill, or
        mid-decode. Takes the Request or its ``.id``. True when the
        request was live and is now terminal ``cancelled`` (slot + blocks
        released); False when it was already terminal or unknown. Takes
        effect immediately (host state only, safe between steps); the
        next step() includes it in the returned terminal list."""
        if not isinstance(req, Request):
            rid, req = req, None
            for cand in list(self._queue) \
                    + [st.req for st in self._prefilling.values()] \
                    + [r for r in self._slot_req if r is not None]:
                if cand.id == rid:
                    req = cand
                    break
            if req is None:
                return False
        if req.finished:
            return False
        if self._queue.remove(req):
            self._terminalize(req, "cancelled", "cancelled while queued",
                              None, where="queue")
            return True
        for slot, st in list(self._prefilling.items()):
            if st.req is req:
                self._release_slot_state(slot, "cancel")
                self._terminalize(req, "cancelled",
                                  "cancelled mid-prefill", None,
                                  where="prefill")
                return True
        for slot in range(self.max_slots):
            if self._slot_req[slot] is req:
                self._release_slot_state(slot, "cancel")
                self._terminalize(req, "cancelled",
                                  "cancelled mid-decode", None,
                                  where="decode")
                return True
        return False                     # not this engine's request

    # --------------------------------------------------------------- drain

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """Drain complete: the door is closed and nothing is in flight."""
        return self._draining and not self._queue and not self._prefilling \
            and not self._live.any() and not self._terminal_buf

    def begin_drain(self, grace_s: Optional[float] = None):
        """Close the door (further submits answer ``rejected_draining``),
        bounce the waiting queue, and let live slots finish — or expire
        them once ``grace_s`` runs out. Idempotent; takes effect at step
        boundaries. Use ``drain()`` to also run the steps."""
        if self._draining:
            return
        self._unforeseen("drain")
        self._draining = True
        self._drain_reported = False
        self._drain_t0 = self._clock()
        self._drain_deadline = None if grace_s is None \
            else self._drain_t0 + float(grace_s)
        mon = _monitor._active
        if mon is not None:
            mon.serve_drain_begin(self.live_count + len(self._prefilling),
                                  len(self._queue), grace_s)

    def _drain_step(self, now: float, finished: List[Request]):
        """The draining replacement for admission: every still-queued
        request leaves as ``rejected_draining`` (a preemption re-queue
        during drain included — deterministic beats half-admitted), and
        grace exhaustion expires whatever is still on a slot."""
        for req in self._queue.drain_all():
            self._terminalize(req, "rejected_draining",
                              "engine is draining (shutdown in progress)",
                              finished)
        if self._drain_deadline is not None and now > self._drain_deadline:
            for slot in list(self._prefilling):
                st = self._prefilling[slot]
                self._release_slot_state(slot, "drain")
                self._terminalize(st.req, "expired",
                                  "drain grace exceeded mid-prefill",
                                  finished, where="drain")
            for slot in range(self.max_slots):
                req = self._slot_req[slot]
                if req is not None:
                    self._release_slot_state(slot, "drain")
                    self._terminalize(req, "expired",
                                      "drain grace exceeded mid-decode",
                                      finished, where="drain")

    def drain(self, grace_s: Optional[float] = None,
              max_steps: Optional[int] = None) -> List[Request]:
        """Graceful shutdown: ``begin_drain(grace_s)`` + step until
        drained. Returns every request that reached a terminal status
        during the drain. With a grace budget the loop is bounded by
        construction; ``max_steps`` is the extra hard stop for the
        unbounded (grace_s=None) form."""
        self.begin_drain(grace_s)
        out: List[Request] = []
        steps = 0
        while not self.drained:
            if max_steps is not None and steps >= max_steps:
                raise RuntimeError(
                    f"drain() exceeded max_steps={max_steps} with "
                    f"{self.live_count} live / {len(self._prefilling)} "
                    f"prefilling")
            out.extend(self.step())
            steps += 1
        return out

    def drain_on_preemption(self, watcher=None,
                            grace_s: Optional[float] = 30.0):
        """Wire a ``distributed.PreemptionWatcher`` into the serving loop:
        once the watcher records SIGTERM/SIGINT, the next step() begins a
        drain with ``grace_s`` — the process finishes (or expires) its
        live requests instead of dying mid-token. ``watcher=None``
        installs the process-wide watcher. Returns the watcher; the
        serving loop keeps calling step() and exits on ``drained``."""
        if watcher is None:
            from ..distributed import preemption as _preemption
            watcher = _preemption.install()
        self._pw = watcher
        self._pw_grace_s = grace_s
        return watcher

    # ------------------------------------------------------ failure paths

    def _fail_engine(self, exc: BaseException):
        """Deterministic loud failure: a decode/chunk dispatch raised (or
        hung past the watchdog). Every in-flight request terminalizes as
        ``failed`` with slots and blocks released — host state stays
        consistent (check_invariants holds) — and the exception
        propagates out of step(); the scheduler is never silently wedged
        and never decodes onward on a runtime it just caught misbehaving.
        """
        why = f"engine failed: {exc}"
        self._unforeseen("fault")
        # (the plan that was being launched is gone with the step)
        self._discarded = "fault"
        self._picked = None
        for req in self._queue.drain_all():
            self._terminalize(req, "failed", why, None)
        for slot in list(self._prefilling):
            st = self._prefilling[slot]
            self._release_slot_state(slot, "fault")
            self._terminalize(st.req, "failed", why, None)
        for slot in range(self.max_slots):
            req = self._slot_req[slot]
            if req is not None:
                self._release_slot_state(slot, "fault")
                self._terminalize(req, "failed", why, None)
        raise exc

    def _on_hang(self, info: dict, elapsed_s: float):
        """Watchdog thread: the armed dispatch exceeded hang_s and is
        STILL STUCK. Make it loud and attributable now — escalate the
        live requests' traces past head sampling, emit the trace-linked
        WARN naming the executable, flight-dump the monitor ring — so the
        evidence exists even if the call never returns."""
        import warnings
        traces = info.get("traces") or ()
        for tr in traces:
            try:
                tr.escalate("serve_hang")
            except Exception:
                pass
        trace_ids = [tr.trace_id for tr in traces if tr.trace_id]
        mon = _monitor._active
        dump_path = None
        if mon is not None:
            try:
                mon.serve_hang(info.get("kind", "?"), info.get("bucket"),
                               elapsed_s, self._watchdog.hang_s,
                               engine_id=self.engine_id,
                               trace_ids=trace_ids)
                dump_path = mon.dump()
            except Exception:
                pass
        warnings.warn(
            f"serving dispatch hang: {info.get('kind', '?')} executable "
            f"(engine {self.engine_id}, bucket {info.get('bucket')}) "
            f"exceeded {HANG_ENV}={self._watchdog.hang_s}s "
            f"({elapsed_s:.2f}s and counting); traces {trace_ids[:4]}"
            + (f"; flight dump {dump_path}" if dump_path else ""),
            RuntimeWarning)

    def _dispatch_guarded(self, kind: str, bucket, upload, call):
        """Run one dispatch that is waited for (the drafter's verify call)
        under the guardrails: the chaos seam fires first (a ``slow`` lands
        inside the armed window — that is how the watchdog is tested), the
        watchdog brackets the uploads, the call + host sync, and any
        exception or detected hang routes through ``_fail_engine`` so the
        engine fails loudly with consistent state. ``upload()`` makes the
        executable's device arguments under one more
        ``engine/decode_prepare`` span, and ``call(span, *args)``
        dispatches, waits and reads back under ``engine/decode_call``
        (``span``), which is therefore dispatch, device run and read-back
        alone.
        ``call`` must COMMIT the donated pools to the engine itself before
        returning — on the hang path the dispatch completed (the old
        buffers are donated away), so the commit must not depend on this
        function returning normally.
        Returns (what ``call`` returned, the call's span)."""
        wd = self._watchdog
        if wd is not None:
            traces = [r._trace for r in self._slot_req if r is not None]
            traces += [st.req._trace for st in self._prefilling.values()]
            wd.arm(kind=kind, bucket=bucket, engine=self.engine_id,
                   traces=traces)
        try:
            if self._faults is not None:
                self._faults.fire(kind)
            with _trace.span("engine/decode_prepare"):
                args = upload()
            with _trace.span("engine/decode_call") as call_span:
                out = call(call_span, *args)
                del args           # released inside the span that used them
        except Exception as e:
            if wd is not None:
                # a hang that then RAISED: the raise is the failure that
                # propagates; drop the latch so the reused engine's next
                # healthy dispatch doesn't inherit a stale hang verdict
                wd.fired = None
            self._fail_engine(e)
        finally:
            if wd is not None:
                wd.disarm()
        if wd is not None and wd.fired is not None:
            fired, wd.fired = wd.fired, None
            self._fail_engine(EngineHangError(
                f"{fired.get('kind', '?')} dispatch took "
                f"{fired.get('elapsed_s', 0):.2f}s "
                f"(> {HANG_ENV}={wd.hang_s}s); WARN + flight dump emitted "
                f"while it hung"))
        return out, call_span

    # ------------------------------------------------- paged scheduling

    def _cache_attrs(self, slots: int, tokens: int) -> dict:
        """What a call span says of the caches the call goes through: the
        K/V bytes of the live context ``tokens`` it reads and, for a model
        with state entries, the slots whose recurrent state it reads and
        writes, and its bytes."""
        attrs = dict(kv_bytes=int(tokens) * self._kv_bytes)
        if self._has_state:
            attrs.update(state_slots=int(slots),
                         state_bytes=int(slots) * self._state_bytes)
        return attrs

    def _kv_walked(self, sc: int, end: int) -> int:
        """Key positions the chunk executable ``sc`` attends over for a
        slot that holds ``end``: whole trips of the walk
        (``models/hybrid.py::walk_keys``), or the table row's width where
        the chunk attends over the gathered view. Counted for
        ``stats()["prefill_attention"]``."""
        width = self._mbs * self.block_size
        kb = self._prefill_key_block.get(sc)
        walked = -(-end // kb) * kb if kb else width
        self.kv_walked += walked
        self.kv_table += width
        return walked

    def _chunk_len(self, n: int) -> int:
        """Shape of the chunk executable serving a length-n prompt: the
        fixed ``prefill_chunk``, else the monolithic bucket for n (sized as
        if unshared, so prefix sharing never changes which executable runs
        — sharing must not mint in steady state)."""
        return self.prefill_chunk or self._bucket_for(n)

    def _cow_args(self, copies):
        """(src, dst) block-copy pairs -> fixed-shape [max_slots] int32
        executable arguments, padded with (0, 0) trash no-ops."""
        src = np.zeros(self.max_slots, np.int32)
        dst = np.zeros(self.max_slots, np.int32)
        for i, (s, d) in enumerate(copies):
            src[i], dst[i] = s, d
        return self._dev(src), self._dev(dst)

    def _pool_fetch_adopt(self, req: Request, slot: int,
                          cov: int) -> Optional[dict]:
        """The registry-miss fallthrough of admission: fetch consecutive
        full-block prefixes of ``req`` from the cross-process pool and
        splice them into ``slot``'s table past ``cov`` (a block boundary).
        Returns {"cov", "blocks", "tokens", "fetch_s"} on any adoption,
        None otherwise. Every failure mode — pool miss, stale generation,
        geometry mismatch, torn payload, injected fetch/adopt fault,
        allocation pressure — just STOPS the walk: whatever was spliced
        stands and the caller prefills the remainder (the partial-fetch
        fallback). Never raises."""
        bs = self.block_size
        toks = tuple(int(t) for t in req.prompt)
        n = len(toks)
        k = cov // bs + 1
        if k * bs >= n or k > self._mbs:
            return None
        t0 = time.perf_counter()
        geom = self._pool_geom()
        fetched = []
        while k * bs < n and k <= self._mbs:
            key = toks[:k * bs]
            if key in self._pager._registry:
                break          # a local copy exists: share_prefix's tier
            self.pool_fetches += 1
            if self._faults is not None:
                try:
                    self._faults.fire("fetch")
                except InjectedFault:
                    self.pool_fetch_misses += 1
                    break
            ent = self._kv_pool.get(prefix_digest(key))
            if ent is None:
                self.pool_fetch_misses += 1
                break
            payload, meta = ent
            try:
                if int(meta.get("gen", -1)) != self._pool_gen \
                        or [int(g) for g in (meta.get("geom") or [])] != geom \
                        or int(meta.get("tokens", -1)) != k * bs:
                    raise ValueError("generation/geometry mismatch")
                arr = _snapshot.decode_block(payload, meta)
                arr = arr.reshape([geom[0], 2] + geom[1:])
            except (ValueError, KeyError, TypeError):
                self.pool_fetch_misses += 1
                break
            self.pool_fetch_hits += 1
            fetched.append((key, arr))
            k += 1
        if not fetched:
            return None
        # splice (fires the "adopt" fault site; best-effort prefix)
        blocks = self._pager.adopt_blocks(slot, cov,
                                          [key for key, _ in fetched])
        if not blocks:
            return None
        exe = self._adopt_exe
        if exe is None:
            exe = self._build_adopt()
        for blk, (_, arr) in zip(blocks, fetched):
            self._pools = exe(self._dev(jnp.int32(blk)), self._pools,
                              self._dev(np.ascontiguousarray(arr[:, 0])),
                              self._dev(np.ascontiguousarray(arr[:, 1])))
        for key, _ in fetched[:len(blocks)]:
            # the pool already holds these bytes: never re-export them
            self._exported.add(prefix_digest(key))
        dt = time.perf_counter() - t0
        nb = len(blocks)
        self.pool_adopted_blocks += nb
        self.pool_adopted_tokens += nb * bs
        self.pool_fetch_s += dt
        return {"cov": cov + nb * bs, "blocks": nb, "tokens": nb * bs,
                "fetch_s": dt}

    def _drain_pool_exports(self, budget: int = 4):
        """End-of-step export drain: serialize up to ``budget`` freshly
        parked registered blocks into the pool (device rows -> host ->
        ``snapshot.encode_block`` -> put). Partial-tail keys never export
        (an adopter COWs the tail anyway — only whole blocks are worth
        moving); already-exported digests skip. An injected "export"
        fault (or a pool/master error) skips that block, counted — the
        pool is a cache tier, losing an export costs a future re-prefill,
        nothing else."""
        pager = self._pager
        pool = self._kv_pool
        bs = self.block_size
        while pager.pending_exports and budget > 0:
            blk, key = pager.pending_exports.popitem(last=False)
            if len(key) % bs != 0:
                continue
            dig = prefix_digest(key)
            if dig in self._exported:
                continue
            budget -= 1
            if self._faults is not None:
                try:
                    self._faults.fire("export")
                except InjectedFault:
                    self.pool_export_errors += 1
                    continue
            rows = np.stack([
                np.stack([np.asarray(jax.device_get(pk[blk])),
                          np.asarray(jax.device_get(pv[blk]))])
                for pk, pv in self._pools])       # [L, 2, bs, n_kv, hd]
            payload, meta = _snapshot.encode_block(rows)
            meta.update(gen=self._pool_gen, tokens=len(key),
                        geom=self._pool_geom())
            if pool.put(dig, payload, meta):
                self._exported.add(dig)
                self.pool_exports += 1
            else:
                self.pool_export_errors += 1

    def drop_prefix_cache(self) -> int:
        """Operator hook for a weight swap / tokenizer change: flush the
        pager's parked prefix blocks AND bump the pool generation, so
        neither the local LRU nor the cross-process tier can serve K/V
        computed under the old weights. Returns the number of local
        blocks released."""
        self._unforeseen("blocks")
        n = self._pager.drop_prefix_cache()
        if self._kv_pool is not None:
            self._pool_gen = int(self._kv_pool.bump_generation())
            self._exported.clear()
        return n

    def _try_admit_paged(self, req: Request) -> bool:
        """Assign a slot, adopt any shared prompt prefix, and reserve the
        first chunk's blocks. False = the pool cannot host the first chunk
        right now; the request stays at the head of the queue (the emitted
        ``serve_page_reject`` event carries free-vs-needed so a refusal
        with free >= needed — an allocator bug, not saturation — is
        flaggable downstream)."""
        n = len(req.prompt)
        slot = self._slots.alloc()
        # the head-of-line request retries this path EVERY step while it
        # waits for blocks: snapshot the pager's sharing counters so a
        # refused attempt leaves them untouched (a 100-step wait must not
        # inflate prefix_hits by 100 — the benchmark's hit counters and the
        # summary's hits/admissions figure read these as per-ADMISSION
        # counts)
        ctrs = self._pager.sharing_counters()
        cov = self._pager.share_prefix(slot, req.prompt)
        pool_meta = None
        if self._kv_pool is not None and cov % self.block_size == 0:
            # registry miss past cov: fall through to the cross-process
            # pool. Adoption raises cov, so the needed/free accounting
            # below already counts pool-adopted blocks as coverage — the
            # PR 12 parked-block rule extended one tier down.
            pool_meta = self._pool_fetch_adopt(req, slot, cov)
            if pool_meta is not None:
                cov = pool_meta["cov"]
        end = min(cov + self._chunk_len(n), n)
        copies = self._pager.ensure_writable(slot, cov, end)
        if copies is None:
            needed = self._pager.blocks_needed(slot, cov, end)
            # a refusal is only real saturation when free-list AND parked
            # prefix-cache blocks together could not cover the need — the
            # allocator reclaims from the LRU before ever refusing
            free = self._pager.reclaimable_blocks
            self._pager.release_slot(slot)
            self._pager.restore_sharing_counters(ctrs)
            self._slots.release(slot)
            self.page_rejects += 1
            req._page_rejects += 1
            pool_blocks = pool_meta["blocks"] if pool_meta else 0
            mon = _monitor._active
            if mon is not None:
                mon.serve_page_reject(free, needed,
                                      trace_id=req._trace.trace_id,
                                      pool_blocks=pool_blocks)
            req._trace.event("page_reject", free=int(free),
                             needed=int(needed), pool_blocks=pool_blocks)
            if free >= needed:
                # refusal WITHOUT real pressure is the allocator-bug
                # signature — this trace must survive head sampling
                req._trace.escalate("page_reject")
            return False
        self._slot_seq[slot] = next(self._admit_seq)
        self._prefilling[slot] = _PrefillState(req, cov, copies)
        req.slot, req.status = slot, "prefilling"
        ph = req._trace_phase("prefill", t0=self._close_queue_phase(req, slot),
                              slot=slot, prefix_hit_tokens=int(cov))
        if self._pager.last_adopt_parked:
            # blocks revived from the persistent prefix cache: this
            # admission's prefill compute shrank by lru_hit_tokens
            ph.set(lru_hit_blocks=self._pager.last_adopt_parked,
                   lru_hit_tokens=self._pager.last_adopt_parked_tokens)
        if pool_meta is not None:
            # TTFT attribution: the pool fetch is ITS OWN slice of the
            # prefill phase, so a TTFT regression decomposes into
            # fetch-bytes time vs prefill-compute time downstream
            ph.set(pool_hit_blocks=int(pool_meta["blocks"]),
                   pool_hit_tokens=int(pool_meta["tokens"]),
                   pool_fetch_s=round(pool_meta["fetch_s"], 6))
            ph.event("pool_fetch", blocks=int(pool_meta["blocks"]),
                     tokens=int(pool_meta["tokens"]),
                     dur_s=round(pool_meta["fetch_s"], 6))
        if copies:
            ph.event("cow", n=len(copies))
        return True

    def _chunk_inputs(self, st: _PrefillState, sc: int, p0: int, end: int):
        """The chunk executable's ids (padded to ``sc``) for prompt
        positions [p0, end), and the slot's pending COW pairs."""
        ids = np.zeros((1, sc), np.int32)
        ids[0, :end - p0] = st.prompt[p0:end]
        return (ids,) + self._cow_args(st.pending_copies)

    def _chunk_launched(self, st: _PrefillState, slot: int, end: int):
        """The chunk covering ``slot``'s prompt up to ``end`` is with the
        device: its COW copies went with it, and after a final chunk the
        slot's cursor stands at the prompt's end."""
        st.sent = end
        st.pending_copies = []
        if end >= st.n:
            self._pos[slot] = st.n

    def _chunk_done(self, st: _PrefillState, slot: int, sc: int, end: int,
                    n_cow: int, tok0, l_ok: bool, ran, finished):
        """Host bookkeeping after one chunk's executable has returned (it
        was on the device during ``ran``, two instants); on the final
        chunk, the first token and the promotion to decode."""
        p0 = st.done
        chunk_s = ran[1] - ran[0]
        st.prefill_s += chunk_s
        mon = _monitor._active
        if mon is not None:
            mon.serve_prefill_step(chunk_s, sc, tokens=end - p0,
                                   engine_id=self.engine_id, span=ran)
        st.done = end
        st.chunks += 1
        st.req._phase.event("chunk", p0=int(p0), end=int(end),
                            dur_s=round(chunk_s, 6), cow=n_cow)
        if not l_ok:
            # non-finite logits: this chunk's cached K/V are garbage —
            # terminalize now instead of prefilling further (or streaming)
            req = st.req
            self._nan_logits(req, "chunk")
            self._release_slot_state(slot, "nan")
            self._terminalize(req, "failed", "non-finite logits (nan)",
                              finished, where="chunk")
            return
        if end < st.n:
            return                         # more chunks next iteration
        req = st.req
        self._pager.register_prompt(slot, st.prompt)
        del self._prefilling[slot]
        t = int(tok0)
        req.prefill_chunks = st.chunks     # counted by the prefix-cache gate
        req.status = "running"
        req.t_first_token = time.time()
        req.tokens.append(t)
        self.tokens_generated += 1
        self._tok[slot] = t
        self._live[slot] = True
        self._slot_req[slot] = req
        if self.drafter is not None:
            # (re-)admission resets drafter state with the token history
            self.drafter.begin_request(req)
        mon = _monitor._active
        if mon is not None:
            mon.serve_admitted(req.t_first_token - req.t_submit, sc,
                               st.prefill_s)
        req._phase.set(chunks=st.chunks)
        req._trace_phase("decode")
        req._trace.set(ttft_s=round(req.t_first_token - req.t_submit, 6))
        if req._stop_hit():
            self._finish(req, finished)

    def _youngest_victim(self, requester: int) -> Optional[int]:
        """Pool-pressure victim: the YOUNGEST tenant, the requester
        included — a newly admitted request must never starve an older one
        off its blocks (the oldest tenant is therefore never evicted and
        always progresses, which is what makes eviction churn terminate).
        """
        cands = [s for s in range(self.max_slots)
                 if s == requester or self._live[s]
                 or s in self._prefilling]
        return max(cands, key=lambda s: self._slot_seq[s], default=None)

    def _preempt(self, slot: int):
        """Pool pressure: evict the tenant of ``slot`` back to the FRONT of
        the queue (its blocks free immediately; its compute is redone on
        re-admission — vLLM's recompute-style preemption)."""
        st = self._prefilling.get(slot)
        req = st.req if st is not None else self._slot_req[slot]
        self._release_slot_state(slot, "preempt")
        req.status, req.slot = "queued", None
        req.tokens = []
        req.t_first_token = None
        req.preemptions += 1
        self._queue.push_front(req)
        self.preemptions += 1
        # requeue episode: whatever phase was running ends and a fresh
        # queue phase opens at the same instant
        req._trace.event("preempt", nth=req.preemptions)
        self._open_queue_phase(req, requeue=True, nth=req.preemptions)
        mon = _monitor._active
        if mon is not None:
            mon.serve_preempted(req.preemptions,
                                trace_id=req._trace.trace_id)

    def _ensure_or_evict(self, slot: int, start: int, end: int):
        """ensure_writable with pool-pressure eviction: preempt youngest
        tenants until the range fits. Returns the COW copies, or None when
        ``slot`` was itself the youngest and got preempted (its request is
        back at the head of the queue)."""
        while True:
            copies = self._pager.ensure_writable(slot, start, end)
            if copies is not None:
                return copies
            victim = self._youngest_victim(slot)
            assert victim is not None
            self._preempt(victim)
            if victim == slot:
                return None

    def _decode_tables(self, rows):
        """The block tables the decode executable may write through: a slot
        the step does not advance (``rows`` is the mask of those it does —
        a slot mid-prefill sits at pos 0 with its real row) gets the trash
        row, or every decode step run while its prompt is still being
        chunked would write a stale token's K/V at position 0 of its first
        — possibly shared — block."""
        return np.where(rows[:, None], self._pager.tables,
                        np.int32(TRASH_BLOCK))

    # ------------------------------------------------- the prepared step

    def _step_planned(self, finished: List[Request], whole):
        """One step of the engine.

        1. LAUNCH the plan made under the last step: its chunk calls, then
           its decode call, back to back, nothing read back between them
           (``engine/prefill_call``, ``engine/decode_call``: launch only).
        2. PREPARE the next step while the device runs (``_plan_step``):
           admission into the slots free now, every prefill's next chunk,
           the decode rows (``ensure_writable`` at the cursor each will
           have), and all of it uploaded. Nothing in it waits for the
           device.
        3. COLLECT (``engine/collect``): the one wait and the one
           read-back of the step; then the chunks' and the decode's
           bookkeeping, as ever (``_chunk_done``, ``_decode_done``).

        The decode's tokens never wait for the host: a step's picked
        tokens are the next step's ``tok`` as they lie on the device, with
        the first token of a slot a chunk has just promoted written in by
        ``note_first_token``. The host's copies (``_tok``, ``req.tokens``)
        are filled at collect.

        A plan holds for the state it was made from. What it could not
        foresee (``_unforeseen``: a stop before the length asked for, a NaN
        row, cancel, expiry, drain, a fault, ``drop_prefix_cache``) throws
        it away, and this step is then built here, before its launch, from
        the state as it is: today's cost for that one step (``plan`` =
        ``rebuilt`` on the ``engine/step`` span, with its ``cause``). Pool
        pressure is never resolved while a step is on the device: the plan
        is given up (cause ``blocks``) and the next step evicts before its
        launch. With nothing prepared and nothing on the device the step
        is built here too (``sync``): a request that finds the engine idle
        is not kept waiting a step.

        With a drafter the plan holds the chunks alone and none is made
        ahead (every step reads ``sync``): how wide a verify call is
        depends on the tokens drafted from the ones before, so after the
        collect each live slot drafts, verifies and is waited for in turn
        (``_decode_spec``)."""
        plan, self._plan = self._plan, None
        cause, self._discarded = self._discarded, None
        evicted = self.preemptions
        if plan is not None:
            how = "prepared"
        else:
            how = "rebuilt" if cause else "sync"
            plan = self._plan_step(finished, None)
        if plan is not None:
            whole.set(plan=how,
                      **({"cause": cause} if how == "rebuilt" else {}))
            self.plan_counts[how] += 1
            if how == "rebuilt":
                self.plan_causes[cause] = self.plan_causes.get(cause, 0) + 1
            self._run_plan(plan, finished, evicted)
        if self.drafter is not None and self._live.any():
            self._decode_spec(finished)

    def _run_plan(self, plan: _Plan, finished: List[Request], evicted: int):
        """Launch ``plan``, prepare the next step under it, collect, and
        book what the calls returned. ``evicted``: the engine's preemptions
        before this step was built, if it was built just now."""
        wd = self._watchdog
        launched: List[_ChunkCall] = []
        try:
            self._launch(plan, launched)
            if self.drafter is not None:
                pass          # never ahead: built in turn (_step_planned)
            elif self.preemptions == evicted:
                self._plan = self._plan_step(finished, plan)
            else:
                # this step had to evict to be built: the pool is short,
                # and a plan made now would take the victim straight back
                # in. The next step is built when its turn comes, as this
                # one was.
                self._discarded = "preempt"
            if wd is not None:
                # the clock starts again for the wait: what the host took
                # to prepare the next step is not the device hanging
                wd.arm(keep=True, **self._armed)
            with _trace.span("engine/collect") as wait:
                # the wait and the step's one read-back; inside the armed
                # window: a hang in the device sync is a hang in the call
                d = plan.decode
                # what the NEXT step's stall record takes its deltas from:
                # read here, where the host is about to wait and the
                # device is busy, not at the step's entry, where the
                # device waits for the launch (the four system calls take
                # 60 us on the chip's host)
                _trace.host_clocks()
                got = self._wait_fetch(
                    ([(c.tok0, c.ok) for c in launched],
                     None if d is None else (d.picked, d.ok)),
                    tuple(c.span.span_id for c in launched)
                    + (() if d is None else (d.span.span_id,)))
        except Exception as e:
            if wd is not None:
                # a hang that then RAISED: the raise is the failure that
                # propagates (see _dispatch_guarded)
                wd.fired = None
            self._fail_engine(e)
        finally:
            if wd is not None:
                wd.disarm()
        if wd is not None and wd.fired is not None:
            fired, wd.fired = wd.fired, None
            self._fail_engine(EngineHangError(
                f"{fired.get('kind', '?')} dispatch took "
                f"{fired.get('elapsed_s', 0):.2f}s "
                f"(> {HANG_ENV}={wd.hang_s}s); WARN + flight dump emitted "
                f"while it hung"))
        # when each call was on the device, as far as the host can say:
        # from its launch to the next call's, the last to the end of the
        # wait. Together they cover the step's device time once.
        edges = [c.span.t0 for c in launched]
        if plan.decode is not None:
            edges.append(plan.decode.span.t0)
        edges.append(wait.t1)
        # for the stall record, each call an equal share of the step's
        # device time: which interval a millisecond lands in moves with
        # where the host happened to block (a chunk's time falls to the
        # decode launched behind it), their sum does not
        kinds = [f"chunk{c.sc}" for c in launched]
        if plan.decode is not None:
            kinds.append("decode")
        if kinds:
            share = (edges[-1] - edges[0]) / len(kinds)
            self._step_calls.extend((kind, share) for kind in kinds)
        for i, (c, (tok0, ok)) in enumerate(zip(launched, got[0])):
            with _trace.span("engine/prefill_host", slot=c.slot,
                             tokens=c.end - c.p0):
                self._chunk_done(c.st, c.slot, c.sc, c.end, c.n_cow, tok0,
                                 bool(ok), (edges[i], edges[i + 1]),
                                 finished)
        if plan.decode is not None:
            self._decode_done(plan.decode.rows, got[1],
                              (edges[-2], edges[-1]), finished)

    def _wait_fetch(self, outs, calls: tuple):
        """The two halves of a read-back, a span each. ``engine/wait``
        [``calls``: the ids of the call spans whose results these are]:
        the host copies of every array of ``outs`` are started (they queue
        behind the compute, so the device never waits to be asked), then
        the host blocks on the LAST array, an output of the last call
        launched; it ends when the program knows the device has finished.
        ``engine/fetch`` [``arrays``, ``bytes``]: the read-back itself,
        as the host sees it (``np.asarray`` of each: what
        ``jax.device_get`` does after starting the copies, at a third of
        its host time). Returns ``outs`` as numpy arrays. Between the two
        lies what the span layer takes to seal one span and open the next
        (the fetch span is made before the wait, so nothing else does):
        microseconds, tens of them while a profile is taken, with the
        device idle; the step's ``engine/collect`` holds it, neither
        child does."""
        leaves = jax.tree_util.tree_leaves(outs)
        fetch = _trace.span("engine/fetch", arrays=len(leaves),
                            bytes=sum(a.nbytes for a in leaves))
        with _trace.span("engine/wait", calls=calls):
            for a in leaves:
                a.copy_to_host_async()
            if leaves:
                jax.block_until_ready(leaves[-1])
        with fetch:
            return jax.tree_util.tree_map(np.asarray, outs)

    def _arm(self, kind: str, bucket, first: bool):
        """Fire the chaos seam for one launch, inside the watchdog's
        window: armed at the step's first launch, moved on to each later
        one, open until the step has collected."""
        wd = self._watchdog
        if wd is not None:
            traces = [r._trace for r in self._slot_req if r is not None]
            traces += [st.req._trace for st in self._prefilling.values()]
            self._armed = dict(kind=kind, bucket=bucket,
                               engine=self.engine_id, traces=traces)
            wd.arm(keep=not first, **self._armed)
        if self._faults is not None:
            self._faults.fire(kind)

    def _launch(self, plan: _Plan, launched: List[_ChunkCall]):
        """Hand the plan's calls to the device, chunks first, decode last,
        and note on the host what is now under way: how far each prompt is
        covered, every decoded row's cursor one further."""
        first = True
        for c in plan.chunks:
            if self._prefilling.get(c.slot) is not c.st:
                continue       # preempted by a later ensure of this plan
            self._arm("chunk", c.sc, first)
            first = False
            with _trace.span("engine/prefill_call",
                             path=self._prefill_attention[c.sc],
                             kv_write=self._kv_write[c.sc],
                             kv_walked=self._kv_walked(c.sc, c.end),
                             **self._cache_attrs(1, c.end)) as c.span:
                self._pools, c.tok0, c.ok = self._prefill_exes[c.sc](
                    self._leaf_values(), self._pools, *c.args)
                c.args = None
            self._chunk_launched(c.st, c.slot, c.end)
            launched.append(c)
        if plan.decode is not None:
            self._arm("decode", None, first)
            self._decode(plan.decode)

    def _decode(self, d: _DecodeCall):
        """Hand a plan's decode call to the device."""
        with _trace.span("engine/decode_call", **d.attrs) as d.span:
            tok = d.tok if d.tok is not None else self._picked
            for slot_dev, c in d.firsts:
                tok = self._note_exe(tok, c.tok0, slot_dev)
            self._pools, d.picked, d.ok = self._decode_exe(
                self._leaf_values(), self._pools, d.args[0], tok,
                *d.args[1:])
            d.args = d.tok = None
        self._picked = d.picked
        for slot in d.rows:
            self._pos[slot] += 1
            self._decode_cow.pop(slot, None)

    def _plan_step(self, finished: List[Request],
                   flight: Optional[_Plan]) -> Optional[_Plan]:
        """Make the next step's plan: admit, set up every prefill's next
        chunk and the decode rows, upload the arguments. ``flight`` is the
        step on the device meanwhile (None: nothing is, and pool pressure
        may evict). The host state read here is as of everything LAUNCHED
        (``_PrefillState.sent``, ``_pos``); only token counts lag, by the
        step in flight. Returns None where nothing will need to run, or
        where the plan had to be given up (``_discarded`` then says why)."""
        ahead = flight is not None
        self._drawn = []
        try:
            self._admit_phase(finished)
            chunks = []
            for slot in sorted(self._prefilling,
                               key=lambda s: self._slot_seq[s]):
                st = self._prefilling.get(slot)   # an earlier ensure may evict
                if st is None or st.sent >= st.n:
                    continue     # (its final chunk is on the device)
                c = self._plan_chunk(slot, st, ahead)
                if c is False:
                    return None
                if c is not None:
                    chunks.append(c)
            # (a drafter's verify calls are no part of a plan: _step_planned)
            decode = None if self.drafter is not None \
                else self._plan_decode(chunks, flight)
            if decode is False:
                return None
            if not chunks and decode is None:
                return None
            plan = _Plan(chunks, decode, self._drawn)
            self._drawn = None
            return plan
        finally:
            if self._drawn is not None:
                # given up half-way: the keys drawn so far are the next
                # build's first
                self._spare_keys[:0] = self._drawn
                self._drawn = None

    def _give_up(self, why: Optional[str]) -> bool:
        """A plan cannot be made while a step is on the device: for want of
        blocks (``why``; the next step evicts before its launch), or of an
        executable that has yet to be compiled, which is not done inside
        the watchdog's window (None: the one such step reads ``sync``)."""
        if why is not None:
            self._discarded = self._discarded or why
        return False

    def _plan_chunk(self, slot: int, st: _PrefillState, ahead: bool):
        """``slot``'s next chunk as a call with its arguments uploaded.
        None: the slot itself was preempted for its blocks. False: the
        plan is given up."""
        p0 = st.sent
        sc = self._chunk_len(st.n)
        end = min(p0 + sc, st.n)
        with _trace.span("engine/prefill_host", slot=slot, tokens=end - p0):
            if sc not in self._prefill_exes:
                if ahead:
                    return self._give_up(None)
                self._build_chunk(sc)
            if ahead:
                more = self._pager.ensure_writable(slot, p0, end)
                if more is None:
                    return self._give_up("blocks")
            else:
                more = self._ensure_or_evict(slot, p0, end)
                if more is None or slot not in self._prefilling:
                    return None
            # pending until the call is launched: a plan thrown away in
            # between leaves them to the one built in its place
            st.pending_copies += more
            ids, src, dst = self._chunk_inputs(st, sc, p0, end)
            args = (self._dev(self._pager.tables.copy()), self._dev(ids),
                    self._slot_index(slot), self._dev(np.int32(p0)),
                    self._dev(np.int32(end)), src, dst, self._next_key())
            return _ChunkCall(slot, st, sc, p0, end, len(st.pending_copies),
                              args)

    def _decode_row(self, slot: int, chunks: dict, flight: Optional[_Plan]):
        """Whether the decode being planned advances ``slot``: (request,
        cursor, the chunk call whose first token is the row's token and is
        not in the step in flight's picked tokens, or None), or None for a
        slot it leaves alone. A request that has, with what is launched,
        the tokens it asked for is left alone: it ends at the next collect
        at the latest."""
        flying = flight is not None and flight.decode is not None \
            and slot in flight.decode.rows
        first = None
        if self._live[slot]:
            req = self._slot_req[slot]
            tokens, pos = len(req.tokens) + flying, int(self._pos[slot])
        else:
            st = self._prefilling.get(slot)
            if st is None:
                return None
            req = st.req
            if slot in chunks:
                # its final chunk is in THIS plan: the decode right behind
                # it takes the slot along, unless its first token can end
                # the request (then it joins once that token is known to
                # be none such: no step runs past a request's end)
                if req.eos_token_id is not None:
                    return None
                first, tokens, pos = chunks[slot], 1, st.n
            elif st.sent >= st.n:
                # its final chunk is on the device, and so is the decode
                # behind it if that took the slot along
                tokens, pos = 1 + flying, int(self._pos[slot])
                if not flying:
                    first = next(c for c in flight.chunks if c.st is st)
            else:
                return None
        if tokens >= req.max_new_tokens:
            return None
        return req, pos, first

    def _plan_decode(self, chunks: List[_ChunkCall],
                     flight: Optional[_Plan]):
        """The decode call with its arguments uploaded, None where no slot
        is to be advanced, False where the plan is given up."""
        ahead = flight is not None
        final = {c.slot: c for c in chunks if c.final}
        with _trace.span("engine/decode_prepare") as prep:
            if self._decode_exe is None:
                if ahead:
                    return self._give_up(None)
                self._build_decode()
            # make every row's write target private + present. A preempted
            # victim's pending copies are DROPPED with it (_release_slot_
            # state) — its freed blocks may be re-handed to the very slot
            # being ensured
            rows = {}          # slot: (request, cursor, first token's chunk)
            preempted = 0
            slot = 0
            while slot < self.max_slots:
                row = self._decode_row(slot, final, flight)
                if row is None:
                    slot += 1
                    continue
                req, pos, first = row
                c = self._pager.ensure_writable(slot, pos, pos + 1)
                if c is None:
                    if ahead:
                        return self._give_up("blocks")
                    victim = self._youngest_victim(slot)
                    self._preempt(victim)
                    preempted += 1
                    rows.pop(victim, None)
                    if victim == slot:      # self-preempted: skip this row
                        slot += 1
                    continue                # else retry the same slot
                if c:
                    self._decode_cow.setdefault(slot, []).extend(c)
                    req._phase.event("cow", n=len(c))
                rows[slot] = row
                slot += 1
            copies = [p for s in rows for p in self._decode_cow.get(s, ())]
            prep.set(live=len(rows), cow=len(copies), preempted=preempted)
            if not rows:
                return None
            mask = np.zeros(self.max_slots, bool)
            pos = np.zeros(self.max_slots, np.int32)
            for s, (_, cursor, _) in rows.items():
                mask[s], pos[s] = True, cursor
            attrs = dict(path=self._decode_attention,
                         kv_write=self._kv_write["decode"],
                         **self._decode_geometry,
                         **self._cache_attrs(len(rows),
                                             int((pos[mask] + 1).sum())))
            if self._has_state:
                attrs["state_path"] = self._decode_state
            # the live KV blocks this step has to read; the gather path read
            # max_slots * max_blocks_per_slot whatever this says
            attrs["kv_blocks"] = int((pos[mask] // self.block_size + 1).sum())
            src, dst = self._cow_args(copies)
            # which slots the step may advance (see _build_decode)
            end = (self._dev(pos + mask),) if self._tells_live else ()
            args = (self._dev(self._decode_tables(mask)), self._dev(pos),
                    src, dst, self._next_key()) + end
            on_device = ahead and flight.decode is not None
            return _DecodeCall(
                {s: req for s, (req, _, _) in rows.items()},
                None if on_device else self._dev(self._host_tok()),
                [(self._slot_index(s), first)
                 for s, (_, _, first) in rows.items() if first is not None],
                args, attrs)

    def _decode_done(self, rows: dict, got, ran, finished):
        """Book the tokens of one decode step over ``rows`` ({slot: its
        request}; their cursors moved on when it was launched). ``got``:
        its picked tokens and finite-logits flags, read back; it was on
        the device during ``ran``."""
        nxt, l_ok = got
        with _trace.span("engine/decode_finish") as fin:
            if len(nxt) > self.max_slots:
                # the routed layers' counts ride behind the tokens
                moe = nxt[self.max_slots:].astype(np.int64)
                self.moe_counts = moe if self.moe_counts is None \
                    else self.moe_counts + moe
                fin.set(**{f"moe_{n}": int(v)
                           for n, v in zip(self._moe_names, moe)})
            live = n_tok = n_done = 0
            for slot, req in rows.items():
                if self._slot_req[slot] is not req:
                    # its final chunk, launched right ahead of this step,
                    # turned out non-finite: the request has failed, the
                    # row is dropped, and what it wrote lies in blocks
                    # released with the request
                    continue
                live += 1
                if not bool(l_ok[slot]):
                    # this slot's logits went non-finite: fail ITS request
                    # and free the slot; the rest of the batch streams on
                    # untouched
                    self._nan_logits(req, "decode")
                    self._release_slot_state(slot, "nan")
                    self._terminalize(req, "failed",
                                      "non-finite logits (nan)", finished,
                                      where="decode")
                    continue
                t = int(nxt[slot])
                req.tokens.append(t)
                self.tokens_generated += 1
                n_tok += 1
                self._tok[slot] = t
                if req._stop_hit():
                    self._finish(req, finished)
                    n_done += 1
            self.decode_steps += 1
            fin.set(tokens=n_tok, finished=n_done)
            mon = _monitor._active
            if mon is not None:
                mon.serve_step(ran[1] - ran[0], live, len(self._queue),
                               engine_id=self.engine_id, span=ran)
                mon.serve_paged(self._pager.stats(), self.kv_util(),
                                engine_id=self.engine_id)

    def _decode_spec(self, finished: List[Request]):
        """Speculative decode step: per live slot, draft up to
        ``_spec_width - 1`` tokens, verify the carried token + all drafts
        in ONE chunk-shaped dispatch, emit the longest agreeing prefix
        plus the verifier's bonus token. Every emitted token is bitwise
        the token sequential greedy decode would have picked, so eos and
        max_new_tokens are simply re-checked after each appended token —
        both can land mid-batch and clip the advance.

        Block discipline: the guaranteed single-token target gets the
        batched-decode treatment (ensure_writable + preemption retry);
        the DRAFT positions get a best-effort reservation that never
        preempts — speculation must not evict a live tenant, it just
        shrinks k to what the pool can back — and is exactly rolled back
        past the accepted cursor after the verify returns (COW sources
        re-referenced, fresh extensions re-trashed). Rejected drafts'
        K/V writes die with the rolled-back blocks or sit above the
        cursor where the next dispatch overwrites them before any read."""
        exe = self._verify_exe
        if exe is None:
            exe = self._build_verify()
        vw = self._spec_width
        drafter = self.drafter
        stepped = False
        for slot in range(self.max_slots):
            if not self._live[slot]:
                continue
            req = self._slot_req[slot]
            p = int(self._pos[slot])
            with _trace.span("engine/decode_prepare", slot=slot) as prep:
                copies = self._ensure_or_evict(slot, p, p + 1)
                if copies is None or not self._live[slot]:
                    continue               # self-preempted: skip this slot
                stepped = True
                remaining = req.max_new_tokens - len(req.tokens)
                k_cap = max(0, min(vw - 1, remaining - 1,
                                   self.max_len - 1 - p))
                drafts = []
                if k_cap > 0:
                    drafts = [int(t) for t in drafter.propose(req, k_cap)]
                    drafts = drafts[:k_cap]
                reservation = []
                if drafts:
                    cov_end, rcopies, reservation = \
                        self._pager.reserve_speculative(slot, p + 1,
                                                        p + 1 + len(drafts))
                    drafts = drafts[:max(0, cov_end - (p + 1))]
                    copies = copies + rcopies
                k = len(drafts)
                ids = np.zeros((1, vw), np.int32)
                ids[0, 0] = self._tok[slot]
                if k:
                    ids[0, 1:1 + k] = drafts
                end = p + 1 + k
                src, dst = self._cow_args(copies)
                prep.set(cow=len(copies))

            def upload():
                return (self._dev(self._pager.tables), self._dev(ids),
                        self._dev(jnp.int32(slot)), self._dev(jnp.int32(p)),
                        self._dev(jnp.int32(end)), src, dst,
                        self._next_key())

            def run(call, *args):
                call.set(kv_write=self._kv_write["verify"])
                self._pools, picked, ok = exe(self._leaf_values(),
                                              self._pools, *args)
                # host readback inside the armed window (see _decode)
                return self._wait_fetch((picked, ok), (call.span_id,))

            # on dispatch failure _fail_engine terminalizes every tenant
            # and releases the pager state — the reservation dies with it
            (out, l_ok), call = self._dispatch_guarded(
                "verify", vw, upload, run)
            self._step_calls.append(("verify", call.dur_s))
            with _trace.span("engine/decode_finish", slot=slot) as fin:
                if not bool(l_ok):
                    # a NaN anywhere in the verify window poisons the
                    # accept test: fail the request (release_slot frees the
                    # speculative reservation with the rest of its blocks)
                    self._nan_logits(req, "verify")
                    self._release_slot_state(slot, "nan")
                    self._terminalize(req, "failed",
                                      "non-finite logits (nan)", finished,
                                      where="verify")
                    continue
                a = 0
                while a < k and int(out[a]) == drafts[a]:
                    a += 1
                n_emit = 0
                for t in drafts[:a] + [int(out[a])]:
                    req.tokens.append(int(t))
                    self.tokens_generated += 1
                    n_emit += 1
                    if req._stop_hit():
                        break
                self._pos[slot] = p + n_emit
                self._tok[slot] = req.tokens[-1]
                if reservation:
                    self._pager.rollback_speculative(slot, p + n_emit,
                                                     reservation)
                req.spec_drafted += k
                req.spec_accepted += a
                self.spec_steps += 1
                self.spec_drafted += k
                self.spec_accepted += a
                self.spec_emitted += n_emit
                drafter.observe(req, a, k)
                fin.set(tokens=n_emit)
                mon = _monitor._active
                if mon is not None:
                    mon.serve_spec_step(
                        call.dur_s, k, a, n_emit, vw, drafter.name,
                        live=self.live_count, queue_depth=len(self._queue),
                        accepted_per_step=self.spec_emitted
                        / self.spec_steps,
                        hit_rate=(self.spec_accepted / self.spec_drafted
                                  if self.spec_drafted else 0.0),
                        engine_id=self.engine_id, span=(call.t0, call.t1))
                if req._stop_hit():
                    self._finish(req, finished)
        if not stepped:
            return
        self.decode_steps += 1
        mon = _monitor._active
        if mon is not None:
            mon.serve_paged(self._pager.stats(), self.kv_util(),
                                engine_id=self.engine_id)

    def _finish(self, req: Request, finished: List[Request]):
        # a stop at the length asked for is the one a prepared step knows of
        self._release_slot_state(
            req.slot,
            None if len(req.tokens) >= req.max_new_tokens else "stop")
        self._deadline_reqs.discard(req)
        req.status, req.t_done = "done", time.time()
        self._retire_id(req)
        finished.append(req)
        mon = _monitor._active
        if mon is not None:
            mon.serve_done(len(req.tokens), req.t_done - req.t_submit,
                           "done")
            if self.drafter is not None and req.spec_drafted:
                mon.serve_spec(self.drafter.name, req.spec_drafted,
                               req.spec_accepted, len(req.tokens),
                               trace_id=req._trace.trace_id)
        # the decode span runs from the first token to the last: both
        # instants (seconds since submit) and the count, once
        mono = time.perf_counter()
        born = req._trace.t0
        req._phase.set(tokens=len(req.tokens),
                       first_token_s=round(req._phase.t0 - born, 6),
                       last_token_s=round(mono - born, 6))
        req._trace_phase(None, t0=mono)
        req._trace.end(t1=mono, status="done", tokens=len(req.tokens),
                       preemptions=req.preemptions)

    # ------------------------------------------------------------- insight

    def kv_util(self) -> float:
        """Live cached tokens / pooled token capacity: the pool's memory
        headroom."""
        cached = int(self._pos[self._live].sum()) \
            + sum(st.done for st in self._prefilling.values())
        cap = self._pager.usable_blocks * self.block_size
        return cached / cap if cap else 0.0

    def door_state(self, top_prefixes: int = 8) -> dict:
        """Cheap, JSON-safe snapshot of this engine's front door — the
        blob an EngineEndpoint publishes to the discovery plane so the
        router places/ejects without ever reaching into engine internals.
        ``state`` is accepting / draining / drained; load is free slots +
        queue depth + active count; ``prefix_keys`` are digests of the
        most recently registered first-block prefixes (cache-aware
        placement matches a new prompt's first block against these)."""
        state = "accepting"
        if self._draining:
            state = "drained" if self.drained else "draining"
        out = {
            "state": state,
            "engine_id": int(self.engine_id),
            "free_slots": int(self._slots.n_free),
            "queue_depth": int(self.queue_depth),
            "active": int(self.active_count),
            "free_blocks": int(self._pager.free_blocks
                               + self._pager.lru_blocks),
            "block_size": int(self.block_size),
            "prefix_keys": self._pager.prefix_digests(top_prefixes),
            "prefix_hits": int(self._pager.prefix_hits),
        }
        # pool tier: generation + hit count travel in the door blob, so
        # the router can prefer warm-pool hosts and spot a generation skew
        out["pool_gen"] = int(self._pool_gen) \
            if self._kv_pool is not None else None
        out["pool_hits"] = int(self._pager.pool_hits) \
            if self._kv_pool is not None else 0
        return out

    def pool_stats(self) -> dict:
        """Cumulative cross-process pool figures (engine side): transfer
        counters plus the pager's splice counters — the ``pool/*`` gauges
        read this."""
        return {
            "gen": int(self._pool_gen),
            "exports": self.pool_exports,
            "export_errors": self.pool_export_errors,
            "fetches": self.pool_fetches,
            "fetch_hits": self.pool_fetch_hits,
            "fetch_misses": self.pool_fetch_misses,
            "fetch_s": round(self.pool_fetch_s, 6),
            "adopted_blocks": self.pool_adopted_blocks,
            "adopted_tokens": self.pool_adopted_tokens,
            "pool_hits": int(self._pager.pool_hits),
            "pool_hit_tokens": int(self._pager.pool_hit_tokens),
            "pending_exports": len(self._pager.pending_exports),
        }

    def stats(self) -> dict:
        out = {
            "compile_count": self.compile_count,
            "executables": 1 + len(self._prefill_exes)
            if self._decode_exe is not None else len(self._prefill_exes),
            "decode_steps": self.decode_steps,
            # what the decode executable was traced with: "paged_kernel"
            # (kernels/pallas/paged_decode.py) or "gather" (the dense view);
            # None before its first trace
            "decode_attention": self._decode_attention,
            # the chunk executables' attention ("key_walk": the slot's key
            # blocks before the call's end; "gather": the whole table row
            # as a view) and the share of their table rows the chunks read
            "prefill_attention": {
                "path": "+".join(sorted(set(
                    self._prefill_attention.values()))) or None,
                "kv_walked": self.kv_walked, "kv_table": self.kv_table,
                "share": round(self.kv_walked / self.kv_table, 4)
                if self.kv_table else None},
            # and with which recurrent-state step: the Pallas kernel's name
            # ("ssd_decode", "gdn_decode") or "scan"; None without state
            "decode_state": self._decode_state,
            # how each executable wrote its rows into the pools ("kernel":
            # kernels/pallas/pool_write.py; "scatter": XLA's), by executable
            # ("decode", a chunk length, "verify")
            "kv_write": {str(k): v for k, v in self._kv_write.items()},
            "tokens_generated": self.tokens_generated,
            "live_slots": self.live_count,
            "queue_depth": self.queue_depth,
            "kv_util": round(self.kv_util(), 4),
            # admissions and the seconds they queued; of those, the ones
            # that waited for KV blocks (the pool, not the slots, was
            # short) and for how long; refusals of the head for blocks
            "queue_waits": self.queue_waits,
            "queue_wait_s_sum": round(self.queue_wait_s_sum, 6),
            "block_waits": self.block_waits,
            "block_wait_s_sum": round(self.block_wait_s_sum, 6),
            "page_rejects": self.page_rejects,
            "guardrails": {
                "expired": self.expired,
                "cancelled": self.cancelled,
                "drains": self.drains,
                "nan_logits": self.nan_logits,
                "draining": self._draining,
                "hang_warns": self._watchdog.hangs
                if self._watchdog is not None else 0,
            },
        }
        if self._has_state:
            out["state"] = {"layers": len(self.spec.state_layers),
                            "bytes_per_slot": self._state_bytes,
                            "slots": self.max_slots}
        if self.moe_counts is not None:
            out["moe"] = dict(zip(self._moe_names,
                                  map(int, self.moe_counts)))
        out["paged"] = dict(self._pager.stats().as_dict(),
                            block_size=self.block_size,
                            preemptions=self.preemptions,
                            prefilling=len(self._prefilling))
        # steps that ran a plan, by how they came by it (_step_planned),
        # and what the rebuilt ones lost theirs to
        out["plan"] = dict(self.plan_counts, causes=dict(self.plan_causes))
        out["stalls"] = self.stalls       # host/stall records sealed
        if self._kv_pool is not None:
            out["pool"] = self.pool_stats()
        if self.drafter is not None:
            out["spec"] = {
                "drafter": self.drafter.name,
                "width": self._spec_width,
                "steps": self.spec_steps,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "emitted": self.spec_emitted,
                "accepted_per_step": round(
                    self.spec_emitted / self.spec_steps, 4)
                if self.spec_steps else 0.0,
                "draft_hit_rate": round(
                    self.spec_accepted / self.spec_drafted, 4)
                if self.spec_drafted else 0.0,
            }
        return out

    def close(self):
        """Stop the watchdog thread (daemonized, so this is hygiene, not
        correctness — long-lived engines can skip it)."""
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None


def generate_via_engine(lm, input_ids, max_new_tokens: int = 32,
                        temperature: float = 1.0, do_sample: bool = False,
                        top_k: int = 0, eos_token_id=None, seed=None,
                        max_length=None):
    """`model.generate(use_engine=True)` backend: run the batch through a
    DecodeEngine and reassemble the eager ``generate()`` output contract
    (``[B, s0 + max_new_tokens]``, finished rows padded with eos).

    ONE engine per model geometry: the cache key is ``(max_slots, max_len,
    quantize, sampling config)`` where max_len is the caller's horizon
    rounded UP to a power-of-two bucket and max_slots is a constant 8 —
    mixed-horizon callers land on the same engine instead of minting a
    fresh executable set per exact (prompt, max_new) pair, and the paged
    engine's chunked prefill serves ANY prompt length through one chunk
    executable (prompt-length buckets are gone). Repeat calls reuse the
    compiled chunk/decode executables; a reused sampling engine just
    restarts its host key stream from ``seed`` (the PRNG key is an
    executable ARGUMENT, not baked in). A cached engine whose leaf list no
    longer matches the model (an in-place int8 swap happened since) is
    dropped rather than served with detached weights."""
    ids_arr = np.asarray(input_ids.numpy() if isinstance(input_ids, Tensor)
                         else input_ids).astype(np.int32)
    b, s0 = ids_arr.shape
    spec = _model_spec(lm)
    # validation + horizon + seed shared with the eager loop (drift = a
    # silent parity break between the two generate() doors)
    m, seed = _resolve_decode_horizon(s0, max_new_tokens, max_length,
                                      spec.max_pos, seed, do_sample)
    if max_new_tokens == 0:
        return Tensor(jnp.asarray(ids_arr))
    slots = 8
    ml = 16
    while ml < m:
        ml *= 2
    ml = max(min(ml, spec.max_pos), m)
    quant = any(str(bf.value().dtype) == "int8"
                for _, bf in lm.named_buffers())
    engines = lm.__dict__.setdefault("_serving_engines", {})
    # the key carries the EFFECTIVE tensor-parallel degree and the chunk
    # size: a mesh appearing (or the model being sharded onto it) after
    # first use must mint a mesh-native engine — the cached single-chip
    # one rebinds the same leaf OBJECTS, so the leaf-identity check below
    # cannot catch a placement-only change and would serve executables
    # whose compiled input shardings no longer match the arrays
    leaves_now = [p for _, p in lm.named_parameters()] \
        + [bf for _, bf in lm.named_buffers()]
    _, tp = serving_mesh(leaves_now)
    chunk = min(32, ml)
    key = (slots, ml, quant, do_sample,
           (float(temperature), int(top_k)) if do_sample else None,
           tp, chunk)
    engine = engines.get(key)
    if engine is not None:
        cur = leaves_now
        if len(cur) != len(engine._leaves) or any(
                a is not b for a, b in zip(cur, engine._leaves)):
            # the model's layer structure changed under the cached engine
            # (e.g. quantize_for_serving swapped Linear -> Int8Linear): its
            # executables rebind the OLD leaf objects — rebuild, don't
            # silently serve pre-swap weights
            engines.pop(key)
            engine = None
    if engine is None:
        if len(engines) >= 4:
            engines.pop(next(iter(engines)))
        engine = DecodeEngine(lm, max_slots=slots, max_len=ml,
                              prefill_chunk=chunk,
                              do_sample=do_sample, temperature=temperature,
                              top_k=top_k, seed=seed)
        engines[key] = engine
    elif do_sample:
        # restart the key stream AND the slot-assignment order: the
        # categorical draw is per batch ROW, so reproducibility needs the
        # same request in the same slot call-over-call (the free list's
        # post-drain order is history-dependent; the engine is idle here)
        engine._key = jax.random.PRNGKey(int(seed))
        engine._spare_keys.clear()
        if engine.live_count == 0 and not engine._queue:
            engine._slots = SlotAllocator(engine.max_slots)
    reqs = [engine.submit(row, max_new_tokens=max_new_tokens,
                          eos_token_id=eos_token_id) for row in ids_arr]
    engine.run()
    eos = -1 if eos_token_id is None else int(eos_token_id)
    fill = max(eos, 0)
    out = np.full((b, s0 + max_new_tokens), fill, np.int32)
    out[:, :s0] = ids_arr
    for i, req in enumerate(reqs):
        if req.status != "done":        # engine-validated batch: can't fail
            raise RuntimeError(f"engine request failed: {req.error}")
        toks = req.output_tokens
        out[i, s0:s0 + len(toks)] = toks   # eos-stopped tails keep the fill
    return Tensor(jnp.asarray(out))
