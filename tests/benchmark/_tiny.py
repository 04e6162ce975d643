"""Drives the harness end to end on the CPU at `gpt_tiny`, from the
fixture tree `tests/benchmark/tiny/` (its own BENCHMARK.json, tiny
configuration and mixes; the stock readers). It skips only the harness's
look for a chip: everything after it is `benchmark.run.run_cell`."""
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def tiny_cell(name, limits=None, root=TINY):
    """A cell of the fixture tree, or of a test's own copy of it."""
    from benchmark.spec import Cell
    root = str(root)
    cell = Cell(name, root=root, here=os.path.join(root, "benchmark"))
    if limits is not None:
        cell.limits = limits
    return cell


def run_tiny(name, seed=3, seconds=1.5, traced=False, faults=None,
             limits=None, root=TINY):
    from benchmark.run import run_cell
    return run_cell(tiny_cell(name, limits, root), seed, seconds, traced,
                    CPU, PEAKS, time.time(), faults)
