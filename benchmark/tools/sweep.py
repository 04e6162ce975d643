"""The one sweep that finds an open-loop cell's knee, the highest rate
the engine sustains (PERF.md holds its table):

    python3 -m benchmark.tools.sweep --workload <cell> --rates 1.2,1.8,2.4 \
        --seconds 45 --seed 5

One process, one model; each rate gets a new engine and one window. Per
rate: the tails, the share of requests that found every slot taken, the
slot occupancy and the queue at the close. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=5)
    a = ap.parse_args(argv)
    from .. import drive_serve, system
    from ..run import Setup, Tracer
    from ..spec import Cell
    from ..trace import Recorder
    if system.device_info()["platform"] != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    system.enable_compile_cache()
    cell = Cell(a.workload)
    model = None
    for k, rate in enumerate(float(r) for r in a.rates.split(",")):
        cell.mix = dict(cell.mix, rate_per_s=rate, check_requests=2)
        rec = Recorder()
        res = drive_serve.run(cell, a.seed + k, a.seconds, rec,
                              Tracer(False, rec), Setup(time.time()),
                              {"model": model, "keep": True})
        model = res["model"]
        f = res["facts"]
        steps = [s for s in f["steps"] if s[1] <= f["t_close"]]
        tail = steps[len(steps) * 2 // 3:]
        waits = sorted(t for t in f["ttft_ms"])
        print(json.dumps({
            "rate_per_s": rate, "requests": f["requests"],
            "finished_by_close_plus_drain": f["finished"],
            "ttft_p50_ms": res["end_to_end"].get("ttft_p50_ms"),
            "ttft_p90_ms": res["end_to_end"].get("ttft_p90_ms"),
            "ttft_mean_ms": res["end_to_end"].get("ttft_mean_ms"),
            "ttft_max_ms": waits[-1] if waits else None,
            "tpot_p90_ms": res["end_to_end"].get("tpot_p90_ms"),
            "out_tokens_per_s": res["end_to_end"]["serve_out_tokens_per_s"],
            "slot_wait_share": 100.0 * f["found_busy"] / f["requests"],
            "occupancy_last_third": 100.0 * sum(s[2] for s in tail)
            / max(len(tail), 1) / f["max_slots"],
            "step_ms_mean": 1e3 * sum(s[1] - s[0] for s in steps)
            / max(len(steps), 1),
            "decode_only_step_ms": 1e3 * sum(
                s[1] - s[0] for s in steps if s[4] == 0 and s[2] > 0)
            / max(sum(1 for s in steps if s[4] == 0 and s[2] > 0), 1),
            "steps_by_waiting": sorted(
                (w, round(1e3 * sum(s[1] - s[0] for s in steps if s[4] == w)
                          / sum(1 for s in steps if s[4] == w), 1),
                 sum(1 for s in steps if s[4] == w))
                for w in {s[4] for s in steps}),
            "per_request_prompt_ttft_late_ms": [
                (n, round((first - due) * 1e3), round(late))
                for (due, first, n, _), late in zip(f["flights"],
                                                   f["lateness_ms"])
                if first is not None],
            "numbers": res["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
