"""Mean host time of one `TrainStep.__call__` (its `train_step/call`
span: prepare + dispatch; the device runs behind it), over the traced
part of the window (program_counter)."""
from ._program import program_spans


def read(ctx):
    steps = program_spans(ctx, "train_step/call")
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
