"""Mean share of the held experts that got at least one token in a decode
step: `moe_touched` of the traced window's `engine/decode_finish` spans
(summed over the layers by the program) over held experts x layers
(program_counter). How near the expert load is to the deployment's: 128
live slots under uniform routing touch about 92 %."""
from ._decode_ops import span_attr_means


def read(ctx):
    work = span_attr_means(ctx, "engine/decode_finish", "moe_touched")
    if work is None:
        return None
    model = ctx["cell"].config["model"]
    return 100.0 * work[0] / (model["num_experts"]
                              * model["num_hidden_layers"])
