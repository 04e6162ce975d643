"""Speculative-decoding drafters for the DecodeEngine (Leviathan et al. 2023).

Decode is memory-bound: one dispatch per token leaves the MXU idle while
the weights stream past. Speculative decoding turns k cheap GUESSES plus
one chunk-shaped VERIFY dispatch into up to k+1 emitted tokens — the
engine's existing ``[1, prefill_chunk]`` chunk machinery already scores k
positions in a single call, so the verifier costs one dispatch no matter
how many drafts ride in it. Greedy acceptance is exact by construction:
a draft is accepted only when the verifier's argmax at the preceding
position IS that draft token, and the first disagreement position's
argmax is emitted as the bonus token — every emitted token is bitwise
the token sequential greedy decode would have produced, so speculation
changes latency, never output.

This file owns the GUESSING side — a small ``Drafter`` interface plus
three implementations spanning the classic design space:

* **PromptLookupDrafter** — n-gram lookup over the request's OWN token
  history (prompt + generated so far), pure host-side string matching
  with no model at all (Saxena's prompt-lookup decoding). Wins hardest
  on summarization/extraction/code-edit shapes where the output quotes
  the input — exactly the shared-prefix workloads the prefix cache
  already serves — and costs microseconds per proposal.
* **DraftModelDrafter** — the classic two-model setup: a small causal LM
  (anything ``_model_spec`` can resolve, GPT or LLaMA) greedily proposes
  k tokens. One fixed-shape ``[1, ctx_len]`` AOT executable per drafter
  (compiled on first use, ``compile_count`` is the sentinel) re-scores a
  sliding window per proposed token — stateless by design, so the draft
  model needs no KV pager of its own and the engine's block accounting
  never learns it exists.
* **EarlyExitDrafter** — self-speculative: the TARGET model drafts with
  a ``recompute_interval``-style stride over its own block stack (every
  ``interval``-th layer), sharing weights with the verifier. No second
  model to train or ship; acceptance tracks how much of the model's
  depth is routinely redundant for the next token.

Drafters never touch executable shapes: proposals are clamped to the
verify executable's width and ride as ids DATA, so the engine's
zero-steady-state-recompile contract holds with any drafter installed.
Per-request drafter state lives in ``Request.drafter_state`` (reset on
preemption along with the tokens it was derived from).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor

__all__ = ["Drafter", "PromptLookupDrafter", "DraftModelDrafter",
           "EarlyExitDrafter"]


class Drafter:
    """Interface the engine drives. ``propose`` may return FEWER than k
    tokens (or none — the engine degrades to a plain one-token verify);
    it must never raise on a well-formed request. ``name`` keys the
    per-drafter monitor counters and the summary's breakdown."""

    name = "drafter"
    max_k = 4          # proposal ceiling; the engine sizes its verify width

    def begin_request(self, req) -> None:
        """A request went live on a slot (re-admission after preemption
        included — its token history restarted, so its drafter state
        must too)."""
        req.drafter_state = {}

    def propose(self, req, k: int) -> List[int]:
        """Up to ``k`` draft tokens continuing ``req.prompt +
        req.tokens``. Called once per speculative step per slot."""
        raise NotImplementedError

    def observe(self, req, accepted: int, drafted: int) -> None:
        """Accept/reject feedback from the verify step (adaptive
        drafters tune k here; the default just keeps counters)."""
        st = req.drafter_state if req.drafter_state is not None else {}
        st["drafted"] = st.get("drafted", 0) + int(drafted)
        st["accepted"] = st.get("accepted", 0) + int(accepted)
        req.drafter_state = st


class PromptLookupDrafter(Drafter):
    """Prompt-lookup / n-gram drafting: find the most recent earlier
    occurrence of the history's trailing n-gram and propose the tokens
    that followed it. No model, no device work — proposals cost a host
    scan of the request's own (short) history. ``max_n`` down to
    ``min_n``: longer matches are more specific, so they are tried
    first."""

    name = "prompt_lookup"

    def __init__(self, max_n: int = 3, min_n: int = 1, max_k: int = 8):
        if not (1 <= min_n <= max_n):
            raise ValueError(f"need 1 <= min_n <= max_n, got "
                             f"({min_n}, {max_n})")
        self.max_n = int(max_n)
        self.min_n = int(min_n)
        self.max_k = int(max_k)

    def propose(self, req, k: int) -> List[int]:
        hist = list(req.prompt) + list(req.tokens)
        n_hist = len(hist)
        k = min(int(k), self.max_k)
        if k < 1:
            return []
        for n in range(self.max_n, self.min_n - 1, -1):
            if n_hist < n + 1:
                continue
            pat = hist[n_hist - n:]
            # newest earlier occurrence wins: recent context predicts the
            # continuation better than a stale one
            for i in range(n_hist - n - 1, -1, -1):
                if hist[i:i + n] == pat:
                    cont = hist[i + n:i + n + k]
                    if cont:
                        return cont
                    break          # match flush at the end: try shorter n
        return []


class _ModelDrafter(Drafter):
    """Shared machinery for drafters that run a causal LM: ONE fixed-shape
    ``[1, ctx_len]`` AOT executable (greedy argmax of the last valid
    position), called k times over a sliding window per proposal. The
    window's absolute positions drift once history exceeds ``ctx_len`` —
    harmless: drafts are guesses, and the verifier is the only party
    whose positions must be exact."""

    def __init__(self, ctx_len: int = 64, max_k: int = 4):
        if ctx_len < 2:
            raise ValueError(f"ctx_len must be >= 2, got {ctx_len}")
        self.ctx_len = int(ctx_len)
        self.max_k = int(max_k)
        self._exe = None
        self._leaves = None
        self._repl = None
        # drafter-side recompile sentinel (the engine's compile_count only
        # counts ENGINE executables; tests gate on both staying flat)
        self.compile_count = 0

    # subclasses: (model, backbone_fn(ids_tensor) -> hidden_tensor,
    #              head_weight, head_transpose, max_pos)
    def _resolve(self):
        raise NotImplementedError

    def _dev(self, x):
        a = jnp.asarray(x)
        return a if self._repl is None else jax.device_put(a, self._repl)

    def _build(self):
        from ..core import dispatch
        from ..models.gpt import _lm_head_logits
        from .engine import serving_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        model, backbone, head_w, transpose, max_pos = self._resolve()
        self.ctx_len = min(self.ctx_len, int(max_pos))
        leaves = [p for _, p in model.named_parameters()] \
            + [b for _, b in model.named_buffers()]
        self._leaves = leaves
        mesh, _ = serving_mesh(leaves)
        self._repl = None if mesh is None else NamedSharding(mesh, P())

        def fn(leaf_arrays, ids, length):
            ctx = dispatch.TraceContext()
            saved = [t._data for t in leaves]
            dispatch.push_trace(ctx)
            try:
                for t, a in zip(leaves, leaf_arrays):
                    t._data = a
                hidden = backbone(Tensor(ids))
                h_last = jax.lax.dynamic_slice_in_dim(
                    hidden.value(), length - 1, 1, axis=1)[:, 0]
                logits = _lm_head_logits(h_last, head_w, transpose)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            finally:
                dispatch.pop_trace()
                ctx.restore()
                for t, d in zip(leaves, saved):
                    t._data = d

        args = (tuple(t.value() for t in leaves),
                self._dev(jnp.zeros((1, self.ctx_len), jnp.int32)),
                self._dev(jnp.int32(1)))
        # eval-mode trace (dropout off) without flipping the model's own
        # flags as a side effect — the engine's _compile_in_eval contract
        layers = model.sublayers(include_self=True)
        modes = [(l, l.training) for l in layers]
        for l in layers:
            l.training = False
        try:
            self._exe = jax.jit(fn).lower(*args).compile()
        finally:
            for l, f in modes:
                l.training = f
        self.compile_count += 1
        return self._exe

    def propose(self, req, k: int) -> List[int]:
        exe = self._exe
        if exe is None:
            exe = self._build()
        k = min(int(k), self.max_k)
        if k < 1:
            return []
        hist = list(req.prompt) + list(req.tokens)
        window = hist[-self.ctx_len:]
        leaf_vals = tuple(t.value() for t in self._leaves)
        out: List[int] = []
        for _ in range(k):
            n = len(window)
            ids = np.zeros((1, self.ctx_len), np.int32)
            ids[0, :n] = window
            t = int(exe(leaf_vals, self._dev(ids), self._dev(jnp.int32(n))))
            out.append(t)
            window.append(t)
            if len(window) > self.ctx_len:
                window.pop(0)
        return out


class DraftModelDrafter(_ModelDrafter):
    """Classic draft-model speculation: a SMALL causal LM proposes, the
    engine's model verifies. Any model ``_model_spec`` resolves works
    (GPT or LLaMA, tied or untied head); its vocabulary should cover the
    target's — out-of-range drafts are never accepted, just wasted."""

    name = "draft_model"

    def __init__(self, model, ctx_len: int = 64, max_k: int = 4):
        super().__init__(ctx_len, max_k)
        self.model = model

    def _resolve(self):
        from .engine import _model_spec
        spec = _model_spec(self.model)
        return (self.model, lambda ids: spec.backbone(ids),
                spec.head_weight, spec.head_transpose, spec.max_pos)


class EarlyExitDrafter(_ModelDrafter):
    """Self-speculative drafting: the TARGET model proposes with a strided
    subset of its own blocks (layers 0, interval, 2*interval, ... — the
    ``recompute_interval`` selection idiom), then verifies at full depth.
    Weights are shared with the engine, so there is nothing extra to
    train, quantize, or shard — under a TP mesh the drafter's executable
    compiles SPMD over the very same placements."""

    name = "early_exit"

    def __init__(self, model, interval: int = 2, ctx_len: int = 64,
                 max_k: int = 4):
        super().__init__(ctx_len, max_k)
        if interval < 1:
            raise ValueError(f"interval must be >= 1, got {interval}")
        self.model = model
        self.interval = int(interval)

    def _resolve(self):
        from .engine import _model_spec
        spec = _model_spec(self.model)
        subset = frozenset(range(0, spec.num_layers, self.interval))
        return (self.model,
                lambda ids: spec.backbone(ids, layer_subset=subset),
                spec.head_weight, spec.head_transpose, spec.max_pos)
