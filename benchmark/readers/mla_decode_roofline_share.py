"""Share of its roofline the latent attention of the decode step reaches:
the latent rows of every live context token read once, every sublayer (the
tokens the program's `engine/decode_call` spans counted through
`kv_bytes`, a step's mean, times the family's `mla_bytes`) and the absorbed
form's two products in one pass (`mla_flops`), whatever passes the kernel
runs, over the device time a decode run spends in the ops the selector
matches (device_trace)."""
import sys

from .. import counts
from ._decode_ops import op_seconds_per_run, span_attr_means


def read(ctx):
    cell = ctx["cell"]
    sel = cell.selector("mla_decode_roofline_share")
    timed = op_seconds_per_run(ctx, sel.get("op_pattern", "mla_decode"))
    work = span_attr_means(ctx, "engine/decode_call", "kv_bytes")
    fam, model = cell.family, cell.config["model"]
    if timed is None or work is None or not hasattr(fam, "mla_flops"):
        return None
    per_token = fam.kv_bytes_per_token(model)
    tokens = work[0] / per_token
    sublayers = per_token / fam.mla_bytes(model, 1)
    share, bound = counts.roofline_share(
        sublayers * fam.mla_flops(model, tokens),
        sublayers * fam.mla_bytes(model, tokens), timed[0],
        ctx["peaks"]["flops_bf16"], ctx["peaks"]["hbm_bytes_per_s"])
    print(f"[bench] mla_decode_roofline_share: {bound}-bound, "
          f"{tokens:.0f} live context tokens a step over {sublayers:.0f} "
          f"sublayers, {timed[1]} decode runs", file=sys.stderr)
    return share
