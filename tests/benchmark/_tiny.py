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


def tiny_cell(name, limits=None):
    from benchmark.spec import Cell
    cell = Cell(name, root=TINY, here=os.path.join(TINY, "benchmark"))
    if limits is not None:
        cell.limits = limits
    return cell


def run_tiny(name, seed=3, seconds=1.5, traced=False, faults=None,
             limits=None):
    from benchmark.run import run_cell
    return run_cell(tiny_cell(name, limits), seed, seconds, traced,
                    CPU, PEAKS, time.time(), faults)
