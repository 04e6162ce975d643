"""Peak device memory on the fullest chip: the larger of the allocator's
`peak_bytes_in_use` and what is live when the window closes plus the
largest temporary allocation of the cell's executables from
`memory_analysis()`, which the allocator does not count (PERF.md
section 5)."""


def read(ctx):
    peak, f = ctx["memory_peak_bytes"], ctx["facts"]
    if not peak:
        return None
    return max(peak, f.get("live_bytes", 0) + f.get("temp_bytes", 0)) / 1e9
