"""paddle_tpu.serving — compiled decode engine with paged KV cache and
continuous batching.

The "millions of users" half of the north star: where ``jit.TrainStep``
compiles the whole training step into one executable per shape bucket,
``serving.DecodeEngine`` does the same for generation — a fixed-shape
decode step over a preallocated block-paged KV cache (zero recompiles
under any admission/eviction pattern) plus chunked or bucketed prefill,
scheduled at iteration granularity (Orca) so short and long requests share
the batch without padding each other out (vLLM's block page table).
Under a "model"-axis mesh with a sharded model the executables go SPMD
(tensor-parallel decode: KV pools head-sharded, page table replicated),
and a persistent LRU prefix cache parks refcount-0 prompt blocks so
repeated system prompts prefill once per process, not once per burst.

    from paddle_tpu.serving import DecodeEngine
    eng = DecodeEngine(model, max_slots=16, max_len=1024)
    req = eng.submit(prompt_ids, max_new_tokens=128, eos_token_id=eos)
    eng.run()                      # or eng.step() inside a serving loop
    print(req.output_tokens)

Failure behavior is a specified contract, not an accident (the guardrail
plane): per-request deadlines (``submit(..., ttft_deadline_s=,
deadline_s=)``), ``cancel()`` from any state, graceful ``drain()`` wired
to SIGTERM via ``drain_on_preemption()``, a dispatch watchdog that WARNs
and fails loudly on a wedged executable call, and the
``PADDLE_SERVE_FAULT`` chaos seam (guardrails.py) that makes every
failure path deterministically testable. Every request ends in exactly
one ``TERMINAL_STATUSES`` member.

Speculative decoding (spec.py): pass ``drafter=`` to the engine and each
decode step drafts k tokens, verifies them all in ONE chunk-shaped
dispatch, and emits the longest agreeing prefix + a bonus token — greedy
output stays bitwise identical to sequential decode, only faster. Three
drafters ship: ``PromptLookupDrafter`` (n-gram over the request's own
history, no model), ``DraftModelDrafter`` (a small causal LM), and
``EarlyExitDrafter`` (the target model at strided depth). Speculative
K/V writes land in pager-reserved blocks and roll back exactly on
rejection.

Fleet front door (router.py + endpoint.py): N engine replicas behind a
stdlib ``Router`` — discovery over the launch KV master (TTL'd
``/{job}/serve/{engine}`` registrations carrying each engine's
``door_state()``), cache-aware placement (prefix-digest affinity first,
least-loaded spill, draining doors excluded), retry with exponential
backoff, heartbeat-staleness + incarnation-ordered health checks,
idempotent requeue-elsewhere on engine death (engine-side request-id
dedup guarantees one id never generates twice), and ``rolling_restart()``
chaining per-engine drains so a fleet upgrade drops nothing. The
``PADDLE_ROUTE_FAULT`` chaos seam (drop/slow/kill at exact route/submit/
status counts) makes the failover contract deterministically testable.
A bounded router-side admission queue (``max_queue=``) parks requests
when every live door is at capacity instead of rejecting, and ``poll()``
streams tokens incrementally (``/status?since=`` cursor).

Cross-process prefix-cache tier (kvpool.py): a per-host shared pool of
exported KV blocks over the launch KV master (``resolve_kv_pool()``;
in-process ``LocalPool`` fallback). Pass ``kv_pool=`` to the engine and
refcount-0 parked blocks export as raw-block snapshots keyed by their
prefix-registry digests; a cold engine's registry miss falls through to
the pool and splices fetched blocks via ``BlockPager.adopt_blocks`` —
a restarted replica re-serves the fleet's shared system prompts without
re-prefilling them. A weight swap (``drop_prefix_cache``) bumps the pool
generation, atomically invalidating every stale entry.

Telemetry: ``serve/*`` counters/gauges/histograms in ``paddle_tpu.monitor``
(QPS, TTFT, per-token latency, slot occupancy, executable mints,
expired/cancelled/drained/hang_warns, spec accepted-per-step/hit-rate)
plus ``route/*`` router counters (affinity_hits, spills, requeues,
ejections) and per-engine ``serve/prefix_hits.eng<id>`` attribution.
"""
from .endpoint import (DoorServer, EngineEndpoint, KVDirectory,
                       LocalDirectory)
from .engine import (DecodeEngine, Request, generate_via_engine,
                     quantize_for_serving)
from .guardrails import (DispatchWatchdog, EngineHangError, FaultSchedule,
                         InjectedFault, InjectedRouteFault,
                         RouteFaultSchedule)
from .kvpool import KVPool, LocalPool, resolve_kv_pool
from .pager import BlockPager, prefix_digest
from .router import (EngineDown, HTTPEngineClient, LocalEngineClient,
                     NoEngineAvailable, Router, RouteTicket)
from .scheduler import TERMINAL_STATUSES, AdmissionQueue, SlotAllocator
from .spec import (Drafter, DraftModelDrafter, EarlyExitDrafter,
                   PromptLookupDrafter)

__all__ = ["DecodeEngine", "Request", "generate_via_engine",
           "quantize_for_serving", "AdmissionQueue", "SlotAllocator",
           "BlockPager", "TERMINAL_STATUSES", "FaultSchedule",
           "InjectedFault", "DispatchWatchdog", "EngineHangError",
           "Drafter", "PromptLookupDrafter", "DraftModelDrafter",
           "EarlyExitDrafter",
           "Router", "RouteTicket", "LocalEngineClient", "HTTPEngineClient",
           "EngineDown", "NoEngineAvailable", "RouteFaultSchedule",
           "InjectedRouteFault", "EngineEndpoint", "DoorServer",
           "LocalDirectory", "KVDirectory", "prefix_digest",
           "KVPool", "LocalPool", "resolve_kv_pool"]
