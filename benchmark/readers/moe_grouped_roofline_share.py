"""Share of its roofline the grouped expert matmul reaches in the decode
step: the operations of the assignments that fell on held experts and the
bytes of the experts that got a token (what the program's
`engine/decode_finish` spans counted, a step's mean, times the family's
`moe_flops` and `expert_bytes`, plus the rows in and out), over the device
time a decode run spends in the ops the selector matches (device_trace).
What the model needs for the work counted, whatever implements it."""
import sys

from .. import counts
from ._decode_ops import op_seconds_per_run, span_attr_means


def read(ctx):
    cell = ctx["cell"]
    sel = cell.selector("moe_grouped_roofline_share")
    timed = op_seconds_per_run(ctx, sel.get("op_pattern", "moe_grouped"))
    work = span_attr_means(ctx, "engine/decode_finish", "moe_local",
                           "moe_touched")
    if timed is None or work is None:
        return None
    fam, model = cell.family, cell.config["model"]
    local, touched = work
    nbytes = touched * fam.expert_bytes(model) \
        + 2 * local * model["hidden_size"] * 2
    share, bound = counts.roofline_share(
        fam.moe_flops(model, local), nbytes, timed[0],
        ctx["peaks"]["flops_bf16"], ctx["peaks"]["hbm_bytes_per_s"])
    print(f"[bench] moe_grouped_roofline_share: {bound}-bound, "
          f"{local:.0f} assignments on {touched:.0f} experts a step, "
          f"{timed[1]} decode runs", file=sys.stderr)
    return share
