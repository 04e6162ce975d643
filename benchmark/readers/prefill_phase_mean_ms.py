"""Mean length of the `request/prefill` spans that ended in a first token
(slot assigned to first token), over the requests submitted in the whole
window; with the generator's lateness and the queue wait it decomposes
`ttft_mean_ms.ttft` (program_counter: the engine's own record)."""
from ._program import program_spans, window_requests


def read(ctx):
    mine = window_requests(ctx)
    if not mine:
        return None
    t_open = ctx["facts"]["t_open"]
    done = [s.t1 - s.t0 for s in program_spans(
                ctx, "request/prefill", (t_open, float("inf")))
            if s.trace_id in mine and "chunks" in s.attrs]
    return 1e3 * sum(done) / len(done) if done else None
