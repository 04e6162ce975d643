"""Time the training loop waited inside `DeviceLoader.__next__` (its
`loader/wait` spans), per `train_step/call`, over the traced part of the
window: the loader's own account beside `feed_wait_ms_per_step.train`,
which times the same wait from outside (program_counter)."""
from ._program import program_spans


def read(ctx):
    steps = program_spans(ctx, "train_step/call")
    if not steps:
        return None
    waits = program_spans(ctx, "loader/wait")
    return 1e3 * sum(s.t1 - s.t0 for s in waits) / len(steps)
