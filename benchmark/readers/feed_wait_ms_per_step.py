"""Host time the training loop waited in the loader's `next()`, per step,
over the whole window (host_clock)."""


def read(ctx):
    steps = ctx["facts"].get("steps")
    if not isinstance(steps, int) or not steps:
        return None
    return 1e3 * ctx["rec"].total("feed_wait") / steps
