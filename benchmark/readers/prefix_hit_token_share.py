"""Share of admitted prompt tokens served from the pager's prefix cache
(live sharing + parked blocks), pager stats differenced over the run
(program_counter). The open-loop chat cell is its control: 0."""


def read(ctx):
    f = ctx["facts"]
    if "counters" not in f or not f.get("prompt_tokens"):
        return None
    c0, c1 = f["counters"]
    hit = (c1["shared_tokens"] - c0["shared_tokens"]
           + c1["prefix_hit_tokens"] - c0["prefix_hit_tokens"])
    return 100.0 * hit / f["prompt_tokens"]
