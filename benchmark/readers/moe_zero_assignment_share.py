"""Share of the decode steps' (token, expert) assignments that fell on a
zero-compute expert: sum of `moe_zero` over sum of `moe_assignments` of the
traced window's `engine/decode_finish` spans (program_counter). 256 zero
experts behind 512 real ones read about 33 % under uniform routing; the
compute a token costs falls with it."""
from . import _program


def read(ctx):
    rows = [(s.attrs["moe_zero"], s.attrs["moe_assignments"])
            for s in _program.program_spans(ctx, "engine/decode_finish")
            if "moe_zero" in s.attrs and "moe_assignments" in s.attrs]
    total = sum(a for _, a in rows)
    if not total:
        return None
    return 100.0 * sum(n for n, _ in rows) / total
