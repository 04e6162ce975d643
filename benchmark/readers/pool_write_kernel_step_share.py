"""Share of the traced window's call spans (`engine/prefill_call`,
`engine/decode_call`) whose `kv_write` says the executable wrote its new
rows into the paged pools with the Pallas block-copy kernel (`kernel`;
`scatter` is XLA's, chosen at trace time and otherwise silent). Spans
without the attribute (a program from before the kernel) read as
nothing, not as 0 (program_counter: the engine's own record of what it
traced)."""
from ._pairing import CALLS
from ._program import program_spans


def read(ctx):
    ways = [s.attrs["kv_write"] for name in CALLS
            for s in program_spans(ctx, name) if "kv_write" in s.attrs]
    if not ways:
        return None
    return 100.0 * sum(w == "kernel" for w in ways) / len(ways)
