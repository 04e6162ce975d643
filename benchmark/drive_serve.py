"""A serving cell: one thread offers the mix's load to `DecodeEngine`
through `submit()`/`step()` and stamps, on its own clock, when each
request was due, sent, and seen to have tokens. Open loop: requests are
sent when due whether or not earlier ones have finished. Closed loop:
each of `clients` callers sends its next request when its last is
answered.
"""
from __future__ import annotations

import time

from . import correct, system, traffic
from .trace import Recorder


class Flight:
    """One request in flight, with the benchmark's own stamps."""
    __slots__ = ("spec", "req", "prompt", "due", "sent", "found_busy",
                 "first_t", "first_n", "last_t", "last_n", "done")

    def __init__(self, spec, req, prompt, due, sent, found_busy):
        self.spec, self.req, self.prompt = spec, req, prompt
        self.due, self.sent, self.found_busy = due, sent, found_busy
        self.first_t = self.last_t = None
        self.first_n = self.last_n = 0
        self.done = False

    def observe(self, now: float):
        n = len(self.req.tokens)
        if n > self.last_n:
            if self.first_t is None:
                self.first_t, self.first_n = now, n
            self.last_t, self.last_n = now, n
        if not self.done and self.req.status not in (
                "queued", "prefilling", "running"):
            self.done = True


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of every value (no interpolation)."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, int(-(-q * len(v) // 100)) - 1))]


def run(cell, seed: int, seconds: float, rec: Recorder, tracer, setup,
        faults=None) -> dict:
    cfg, mix = cell.config, cell.mix
    vocab = cfg["model"]["vocab_size"]
    open_loop = mix["loop"] == "open"

    with setup.phase("build"):
        srv = system.Server(cell, seed, (faults or {}).get("model"))
        kept_model = srv.model if faults and "keep" in faults else None
        if faults and "server" in faults:
            faults["server"](srv)
    with setup.phase("compile_and_warm"):
        chunk = cfg["engine"]["prefill_chunk"]
        srv.warm(traffic.rng(seed, 9).integers(
            0, vocab, chunk + chunk // 2).tolist(), 4)
    with setup.phase("traffic"):
        if open_loop:
            plan = traffic.open_loop(mix, seed, seconds, vocab)
            prompts = [r.prompt() for r in plan]
        else:
            source = traffic.closed_loop(mix, seed, vocab)
    c0 = srv.counters()
    setup.close()

    flights, active, steps = [], [], []
    emitted = 0

    def send(spec, prompt, due, now):
        busy = srv.busy() >= srv.max_slots
        with rec.span("submit"):
            req = srv.submit(prompt, spec.new_tokens)
        f = Flight(spec, req, prompt, due, now, busy)
        flights.append(f)
        active.append(f)

    t_open = time.perf_counter()
    t_end = t_open + seconds
    tracer.plan(t_open, seconds, mix.get("trace"))
    nxt = 0
    closing = False
    while True:
        now = time.perf_counter()
        tracer.tick()
        if not closing and now >= t_end:
            closing = True
            t_close = now
            tokens_at_close = emitted
        if open_loop:
            # every request of the plan is due inside the window, so the
            # closing pass still sends what came due during the last step
            while nxt < len(plan) and t_open + plan[nxt].due_s <= now:
                send(plan[nxt], prompts[nxt], t_open + plan[nxt].due_s, now)
                nxt += 1
        elif not closing:
            while len(active) < mix["clients"]:
                spec = next(source)
                send(spec, spec.prompt(), now, now)
        if closing and (now > t_close + 60.0 or all(
                f.first_t is not None or f.done for f in flights)):
            break      # every request due in the window has its first token
        if srv.busy() or srv.live():
            waiting = srv.busy() - srv.live()     # these run a chunk now
            t0 = time.perf_counter()
            with rec.span("engine_step"):
                srv.step()
            t1 = time.perf_counter()
            context = 0
            for f in active:
                before = f.last_n
                f.observe(t1)
                emitted += f.last_n - before
                if f.first_t is not None and not f.done:
                    context += len(f.prompt) + f.last_n
            # (start, end, live slots after it, their context in tokens,
            #  requests that were queued or mid-prefill when it began)
            steps.append((t0, t1, srv.live(), context, waiting))
            active = [f for f in active if not f.done]
        else:
            with rec.span("wait_for_arrival"):
                gap = 0.002 if not open_loop or nxt >= len(plan) else \
                    t_open + plan[nxt].due_s - time.perf_counter()
                time.sleep(max(0.0, min(gap, 0.002)))
    t_stop = time.perf_counter()
    tracer.stop()
    window_s = t_close - t_open
    c1 = srv.counters()
    live = system.memory_live_bytes()
    peak = system.memory_peak_bytes()
    temp = srv.temp_bytes()

    # ---- end-to-end readings, all on the benchmark's clock
    ttft = [(f.first_t - f.due) * 1e3 for f in flights
            if f.first_t is not None]
    missed = [f for f in flights if f.first_t is None]
    tpot = [(f.last_t - f.first_t) / (f.last_n - f.first_n) * 1e3
            for f in flights if f.last_n > f.first_n]
    bad = [f for f in flights if f.done and f.req.status != "done"]
    e2e = {"serve_out_tokens_per_s": tokens_at_close / window_s}
    if ttft:
        # a request that never got a token misses any limit: it ranks last
        e2e["ttft_p90_ms"] = percentile(
            ttft + [float("inf")] * len(missed), 90)
        e2e["ttft_p50_ms"] = percentile(ttft, 50)
        e2e["ttft_mean_ms"] = float("inf") if missed \
            else sum(ttft) / len(ttft)
    if tpot:
        e2e["tpot_p90_ms"] = percentile(tpot, 90)
        # every gap between tokens of every request, as one mean: all the
        # decode time the callers waited over all the tokens they got
        e2e["tpot_mean_ms"] = 1e3 * sum(
            f.last_t - f.first_t for f in flights if f.last_n > f.first_n) \
            / sum(f.last_n - f.first_n for f in flights)

    finished = [{"prompt": f.prompt, "tokens": list(f.req.tokens),
                 "want": f.spec.new_tokens, "doc": f.spec.doc,
                 "prefill_chunks": getattr(f.req, "prefill_chunks", None)}
                for f in flights if f.done and f.req.status == "done"]
    short = sum(1 for r in finished if len(r["tokens"]) != r["want"])
    lateness = [(f.sent - f.due) * 1e3 for f in flights]
    facts = {
        "window_s": window_s - tracer.cost_s, "steps": steps,
        "live_bytes": live, "max_slots": srv.max_slots,
        "requests": len(flights), "finished": len(finished),
        "found_busy": sum(f.found_busy for f in flights),
        "lateness_ms": lateness, "counters": (c0, c1),
        "prompt_tokens": sum(len(f.prompt) for f in flights
                             if f.first_t is not None),
        "ttft_ms": ttft, "tpot_ms": tpot, "temp_bytes": temp,
        "chips": cell.chips,
        "flights": [(f.due, f.first_t, len(f.prompt), f.last_n)
                    for f in flights],
        "t_open": t_open, "t_close": t_close,
    }
    srv.close()

    # ---- the reference, after the window, on a sample drawn from the seed
    t0 = time.perf_counter()
    sample = correct.pick_sample(finished, int(mix["check_requests"]), seed)
    numbers = {}
    if sample:
        (numbers["served_logit_gap"], numbers["served_logit_gap_mean"],
         numbers["tokens_compared"]) = correct.served_token_gaps(
                cell, seed, sample, cfg["engine"]["max_len"])
    numbers["requests_not_done"] = len(bad) + len(missed)
    numbers["answers_of_wrong_length"] = short
    numbers["nan_logits"] = c1["nan_logits"] - c0["nan_logits"]
    numbers["recompiles_in_window"] = c1["compile_count"] \
        - c0["compile_count"]
    quiet = sorted(1e3 * (s[1] - s[0]) for s in steps
                   if s[4] == 0 and s[2] > 0 and s[1] <= t_close)
    notes = [f"{len(flights)} requests, {len(finished)} finished, "
             f"{len(steps)} engine steps; decode-only step (host clock) "
             f"median {quiet[len(quiet) // 2]:.1f} ms over {len(quiet)}"
             if quiet else f"{len(flights)} requests, no decode-only step"]
    # a window that reads slow (PERF.md section 7 item 9) shows here whether
    # a few steps froze or every step was slower
    slow = sorted((1e3 * (s[1] - s[0]) for s in steps), reverse=True)[:3]
    notes.append("longest engine steps (host clock) "
                 + ", ".join(f"{x:.0f}" for x in slow)
                 + f" ms; latest send {max(lateness, default=0.0):.0f} ms "
                   f"after it was due")
    return {
        "notes": notes,
        "attempted": len(flights), "failed": len(bad) + len(missed),
        "numbers": numbers, "reference_s": time.perf_counter() - t0,
        "memory_peak_bytes": peak, "window": (t_open, t_stop),
        "facts": facts, "end_to_end": e2e, "model": kept_model,
        "sample": sample,
    }
