"""Share of its roofline the attention kernel reaches in the train step:
causal attention's FLOPs and least bytes for one step, from the shapes
(the family's count), over the device time per step of the ops the
selector matches (device_trace). The bound that applies is written to standard error."""
import sys

from .. import counts
from .train_step_device_ms import step_runs


def read(ctx):
    tr = ctx["trace"]
    runs = step_runs(ctx)
    if tr is None or not tr.ops or not runs:
        return None
    cell = ctx["cell"]
    sel = cell.selector("flash_roofline_share")
    t0, t1 = tr.t0, tr.t1
    tr.t0, tr.t1 = min(r[1] for r in runs), max(r[2] for r in runs)
    try:
        secs, n = tr.op_seconds(sel["op_pattern"])
    finally:
        tr.t0, tr.t1 = t0, t1
    if not n:
        return None
    model, job = cell.config["model"], cell.mix
    rows = job["batch"] // ctx["facts"]["chips"]
    share, bound = counts.roofline_share(
        cell.family.attention_train_flops(model, rows, job["seq"]),
        cell.family.attention_train_bytes(model, rows, job["seq"]),
        secs / len(runs), ctx["peaks"]["flops_bf16"],
        ctx["peaks"]["hbm_bytes_per_s"])
    print(f"[bench] flash_roofline_share: {bound}-bound, {n} kernel runs "
          f"in {len(runs)} steps", file=sys.stderr)
    return share
