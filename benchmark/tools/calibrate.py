"""On-chip readings that the limits of `correct` are set from (PERF.md
records them). One process, several seeds:

    python3 -m benchmark.tools.calibrate --workload <cell> --seeds 1,2,3 \
        [--control-seeds 2] [--seconds 40] [--mask-nonlive] \
        [--out chiprun_out/<file>.json]

For every seed the program's numbers against the reference (the LOWER
readings), held against the cell's limits as a run would; for the first
`--control-seeds` of them also the control (the reference put in the
program's place, computed with fp8 products) and a planted fault (a
training cell: half of the batch left out, in the reference; a serving
cell: the last served token of the longest sampled request altered),
each pushed through `correct.verdict` (the UPPER readings). Not part of
a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def training(cell, seeds, n_control, _seconds):
    from .. import correct, system, traffic
    from ..reference import common
    cfg, job = cell.config, cell.mix
    model = cfg["model"]
    n_ref = int(job["reference_steps"])
    out = []
    for i, seed in enumerate(seeds):
        rows = lambda j: traffic.train_row(          # noqa: E731
            seed, j, model["vocab_size"], job["seq"])
        t0 = time.time()
        tr = system.Trainer(cell, seed, rows)
        feed = tr.batches()
        got = {"losses": []}
        for s in range(n_ref):
            got["losses"].append(float(tr.step(next(feed))))
            if s == 0:
                got["grad"] = tr.first_grad_norms()
                got["sketch"] = tr.first_grad_sketches()
        got["update"] = tr.update_norms()
        tr.close()
        t1 = time.time()

        def follow(**kw):
            return correct.follow_reference(cell, seed, rows, n_ref, **kw)

        want = follow()
        t2 = time.time()
        def held(read):
            """The readings with a run's verdict on them (the two exact
            counts of a run are not in question here)."""
            rows, ok = correct.verdict(dict(read, recompiles_in_window=0,
                                            last_loss_finite=0.0),
                                       cell.limits)
            return dict(read, correct=ok,
                        failed=[k for k, v, lim in rows
                                if v is None or not v <= lim])

        rec = {"seed": seed,
               "program": held(correct.compare_training(got, want)),
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": got["losses"], "ref_losses": want["losses"]}
        if i < n_control:
            rec["control_fp8"] = held(correct.compare_training(
                follow(dot=common.fp8_dot), want))
            rec["fault_half_batch"] = held(correct.compare_training(
                follow(batch_rows=range(job["batch"] // 2)), want))
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def mask_nonlive(srv):
    """PERF.md, Open questions, item 1: the engine hands the decode
    executable the block-table rows of slots that are still mid-prefill,
    and the step writes a stale K/V at their position 0. This plants the
    one-line cure from outside (those rows point at trash block 0 while
    the decode call runs), so a calibration with and without it shows
    what the fault is worth in the numbers compared."""
    eng = srv.engine
    decode = eng._decode

    def masked(finished):
        tables = eng._pager.tables
        dead = ~np.asarray(eng._live, bool)
        kept = tables[dead].copy()
        tables[dead] = 0
        try:
            return decode(finished)
        finally:
            tables[dead] = kept
    eng._decode = masked


def altered_last_token(sample, vocab, seed):
    """The least an altered token can read: the LAST served token of the
    longest sampled request replaced (nothing follows it, so no other
    position moves)."""
    r = dict(sample[0])
    toks = list(r["tokens"])
    toks[-1] = (toks[-1] + 1 + int(seed) % (vocab - 1)) % vocab
    r["tokens"] = toks
    return [r]


def step_times(facts):
    """Host-clock milliseconds of the window's decode-only steps (no
    prefill chunk in them): median and 90th percentile."""
    ms = sorted(1e3 * (s[1] - s[0]) for s in facts["steps"]
                if s[4] == 0 and s[2] > 0 and s[1] <= facts["t_close"])
    if not ms:
        return None
    return {"n": len(ms), "p50": ms[len(ms) // 2],
            "p90": ms[int(0.9 * (len(ms) - 1))]}


def serving(cell, seeds, n_control, seconds, masked=False):
    from .. import correct, drive_serve
    from ..run import Setup, Tracer
    from ..trace import Recorder
    cfg = cell.config
    vocab = cfg["model"]["vocab_size"]
    pad = cfg["engine"]["max_len"]
    model, out = None, []
    for i, seed in enumerate(seeds):
        rec = Recorder()
        faults = {"model": model, "keep": True}
        if masked:
            faults["server"] = mask_nonlive
        res = drive_serve.run(cell, seed, seconds, rec, Tracer(False, rec),
                              Setup(time.time()), faults)
        model = res["model"]
        rows, ok = correct.verdict(res["numbers"], cell.limits)
        r = {"seed": seed, "masked": masked, "program": res["numbers"],
             "program_correct": ok,
             "end_to_end": res["end_to_end"],
             "reference_s": res["reference_s"],
             "requests": res["attempted"],
             "finished": res["facts"]["finished"],
             "found_busy": res["facts"]["found_busy"],
             "decode_only_step_ms": step_times(res["facts"])}
        if i < n_control and res["sample"]:
            for name, sample, control in (
                    ("control_fp8", res["sample"], True),
                    ("fault_altered_token",
                     altered_last_token(res["sample"], vocab, seed), False)):
                gap, mean, n = correct.served_token_gaps(
                    cell, seed, sample, pad, control=control)
                read = dict(res["numbers"], served_logit_gap=gap,
                            served_logit_gap_mean=mean, tokens_compared=n)
                rows, ok = correct.verdict(read, cell.limits)
                r[name] = {"served_logit_gap": gap,
                           "served_logit_gap_mean": mean,
                           "tokens_compared": n, "correct": ok,
                           "failed": [k for k, v, lim in rows
                                      if v is None or not v <= lim]}
        print(json.dumps(r), flush=True)
        out.append(r)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--mask-nonlive", action="store_true",
                    help="serving: run with the engine's non-live table "
                         "rows masked during decode (PERF.md section 7)")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    from .. import system
    from ..spec import Cell
    cell = Cell(a.workload)
    if system.device_info()["platform"] != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    system.enable_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",")]
    if cell.mix["kind"] == "train":
        out = training(cell, seeds, a.control_seeds, a.seconds)
    else:
        out = serving(cell, seeds, a.control_seeds, a.seconds,
                      a.mask_nonlive)
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
