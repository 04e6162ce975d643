"""Share of requests that found every slot taken when they were sent
(admitted + queued >= max_slots at submit; benchmark-side). The regime
gauge of an open-loop cell: near 0 well under the knee, and the 90th
percentile of time to first token becomes a queueing time once this
nears 10% (PERF.md, Erlang-C argument)."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("requests"):
        return None
    return 100.0 * f["found_busy"] / f["requests"]
