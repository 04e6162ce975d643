"""Share of the traced window's `engine/decode_call` spans whose `path`
says the decode executable attended with the Pallas paged-decode kernel
(`paged_kernel`; `gather` is the dense-view fallback, chosen at trace time
and otherwise silent). Spans without the attribute (a program from before
the kernel) read as nothing, not as 0 (program_counter: the engine's own
record of what it traced)."""
from ._program import program_spans


def read(ctx):
    paths = [s.attrs["path"] for s in program_spans(ctx, "engine/decode_call")
             if "path" in s.attrs]
    if not paths:
        return None
    return 100.0 * sum(p == "paged_kernel" for p in paths) / len(paths)
