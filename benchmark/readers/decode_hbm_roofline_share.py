"""Share of the HBM roofline the decode executable reaches: the bytes the
MODEL needs for a step (its family's count: weights once, the live
context read once, one position's K and V written per live slot; from the
shapes and the live lengths the benchmark itself tracks) over 819 GB/s,
over the device time of the whole executable. Independent of what
implements the step."""
import sys

from .. import counts
from ._common import mean, serve_module_runs, traced_steps


def read(ctx):
    runs = serve_module_runs(ctx).get("decode")
    steps = [s for s in traced_steps(ctx) if s[2] > 0]
    if not runs or not steps:
        return None
    fam, model = ctx["cell"].family, ctx["cell"].config["model"]
    nbytes = mean([fam.decode_step_bytes(model, s[3], s[2]) for s in steps])
    # decode is memory-bound by three decades here; the FLOP term is
    # there so the bound named is the one that applies
    flops = fam.forward_flops(model, mean([s[2] for s in steps]),
                              mean([s[3] for s in steps]))
    share, bound = counts.roofline_share(
        flops, nbytes, mean(runs), ctx["peaks"]["flops_bf16"],
        ctx["peaks"]["hbm_bytes_per_s"])
    print(f"[bench] decode_hbm_roofline_share: {bound}-bound",
          file=sys.stderr)
    return share
