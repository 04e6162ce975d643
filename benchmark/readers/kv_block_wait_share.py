"""Share of the requests submitted in the whole window whose queue span
says they waited longest for KV blocks (`cause == "blocks"`): the pool,
not the slot table, held them. `slot_wait_share.ttft` is taken at submit
and sees only slots (program_counter: the engine's own record)."""
from ._program import window_requests


def read(ctx):
    queued = window_requests(ctx).values()
    if not queued:
        return None
    return 100.0 * sum(s.attrs.get("cause") == "blocks"
                       for s in queued) / len(queued)
