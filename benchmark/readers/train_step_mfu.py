"""The whole train step's share of the chips' peak: the family's analytic
model FLOPs per token (6 per parameter a token multiplies + causal
attention; recompute not counted) x tokens/s over chips x peak
(host_clock + shapes)."""


def read(ctx):
    f = ctx["facts"]
    if "tokens" not in f:
        return None
    cell = ctx["cell"]
    fpt = cell.family.train_flops_per_token(cell.config["model"],
                                            cell.mix["seq"])
    return 100.0 * fpt * f["tokens"] / f["window_s"] \
        / (f["chips"] * ctx["peaks"]["flops_bf16"])
