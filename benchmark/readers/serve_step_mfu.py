"""The whole serving step's share of the chip's peak FLOP/s over the
window: the family's forward FLOPs (2 x the parameters a token multiplies
+ attention) of the tokens processed, prefill and decode, over window x
peak (program_counter + host_clock). Prompt tokens served from the prefix
cache are not processed and do not count."""


def read(ctx):
    f = ctx["facts"]
    if "flights" not in f:
        return None
    cell = ctx["cell"]
    c0, c1 = f["counters"]
    cached = (c1["shared_tokens"] - c0["shared_tokens"]
              + c1["prefix_hit_tokens"] - c0["prefix_hit_tokens"])
    prompt = sum(n for _, first, n, _ in f["flights"] if first is not None)
    done = max(prompt - cached, 0)
    pairs = sum(n * (n + 1) // 2 for _, first, n, _ in f["flights"]
                if first is not None) * (done / prompt if prompt else 0.0)
    in_window = [s for s in f["steps"] if s[1] <= f["t_close"]]
    decode_tokens = sum(s[2] for s in in_window)
    pairs += sum(s[3] for s in in_window)
    flops = cell.family.forward_flops(cell.config["model"],
                                      done + decode_tokens, pairs)
    return 100.0 * flops / (f["window_s"] * ctx["peaks"]["flops_bf16"])
