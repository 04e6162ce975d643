"""Mean time to first token over every request of the run, from when it
was due (host_clock): the steadier statistic that stands beside the
bounded tail `ttft_p90_ms`. It is (chunks of the mean prompt) x (engine
step) plus half a step of waiting for the running step to end."""


def read(ctx):
    t = ctx["facts"].get("ttft_ms")
    return sum(t) / len(t) if t else None
