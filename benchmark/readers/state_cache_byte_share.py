"""Which of a block's two caches a decode step pays for: the bytes of
recurrent state over the bytes of state and of K/V that the traced window's
`engine/decode_call` spans counted (`state_bytes`: the live slots' state;
`kv_bytes`: their live context's keys and values). It falls as contexts
grow (program_counter)."""
from . import _program


def read(ctx):
    rows = [(s.attrs["state_bytes"], s.attrs["kv_bytes"])
            for s in _program.program_spans(ctx, "engine/decode_call")
            if "state_bytes" in s.attrs and "kv_bytes" in s.attrs]
    total = sum(a + b for a, b in rows)
    if not total:
        return None
    return 100.0 * sum(a for a, _ in rows) / total
