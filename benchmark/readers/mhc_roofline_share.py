"""Share of its roofline the hyper-connection boundaries of the decode step
reach: the rows the program's `engine/decode_call` spans say a decode run
mixes (`mhc_rows`: every boundary's rows, a step's mean) times the
family's `mhc_flops` and `mhc_bytes` (a row's streams read and written,
the sublayer's output in, the next input out, each boundary's parameters
once), over the device time a decode run spends in the ops the selector
matches (device_trace). A model without hyper-connections, or a program
whose spans do not carry `mhc_rows`, reads nothing."""
import sys

from .. import counts
from ._decode_ops import op_seconds_per_run, span_attr_means


def read(ctx):
    cell = ctx["cell"]
    sel = cell.selector("mhc_roofline_share")
    fam, model = cell.family, cell.config["model"]
    if not hasattr(fam, "mhc_flops"):
        return None
    timed = op_seconds_per_run(ctx, sel.get("op_pattern", "^mhc_"))
    work = span_attr_means(ctx, "engine/decode_call", "mhc_rows")
    if timed is None or work is None:
        return None
    rows = work[0]
    share, bound = counts.roofline_share(
        fam.mhc_flops(model, rows), fam.mhc_bytes(model, rows), timed[0],
        ctx["peaks"]["flops_bf16"], ctx["peaks"]["hbm_bytes_per_s"])
    print(f"[bench] mhc_roofline_share: {bound}-bound, {rows:.0f} rows "
          f"mixed a decode run, {timed[1]} decode runs", file=sys.stderr)
    return share
