"""Share of its roofline the Mamba-2 decode step reaches: every live slot's
float32 state matrices read once and written once (the slots the program's
`engine/decode_call` spans counted, a step's mean, times the family's
`ssd_state_bytes`) and the recurrence's products, over the device time a
decode run spends in the ops the selector matches (device_trace)."""
import sys

from .. import counts
from ._decode_ops import op_seconds_per_run, span_attr_means


def read(ctx):
    cell = ctx["cell"]
    sel = cell.selector("ssd_decode_roofline_share")
    timed = op_seconds_per_run(ctx, sel.get("op_pattern", "ssd_decode"))
    work = span_attr_means(ctx, "engine/decode_call", "state_slots")
    if timed is None or work is None:
        return None
    fam, model = cell.family, cell.config["model"]
    slots = work[0]
    share, bound = counts.roofline_share(
        fam.ssd_flops(model, slots), 2 * slots * fam.ssd_state_bytes(model),
        timed[0], ctx["peaks"]["flops_bf16"],
        ctx["peaks"]["hbm_bytes_per_s"])
    print(f"[bench] ssd_decode_roofline_share: {bound}-bound, "
          f"{slots:.1f} live slots a step, {timed[1]} decode runs",
          file=sys.stderr)
    return share
