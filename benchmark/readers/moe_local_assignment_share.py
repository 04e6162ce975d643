"""Share of the decode steps' (token, expert) assignments that fell on an
expert this chip holds: sum of `moe_local` over sum of `moe_assignments` of
the traced window's `engine/decode_finish` spans (program_counter). A
quarter of the experts under a full-width router reads about 25 %."""
from . import _program


def read(ctx):
    rows = [(s.attrs["moe_local"], s.attrs["moe_assignments"])
            for s in _program.program_spans(ctx, "engine/decode_finish")
            if "moe_local" in s.attrs and "moe_assignments" in s.attrs]
    total = sum(a for _, a in rows)
    if not total:
        return None
    return 100.0 * sum(n for n, _ in rows) / total
