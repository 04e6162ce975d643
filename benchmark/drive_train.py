"""A training cell: set-up drives the compiled step through its first
updates (which also compile it and feed the comparison), the window then
drives that same object, and the reference follows once the window has
closed and the program's state is freed.
"""
from __future__ import annotations

import time

from . import correct, system, traffic
from .trace import Recorder


def run(cell, seed: int, seconds: float, rec: Recorder, tracer, setup,
        faults=None) -> dict:
    cfg, job = cell.config, cell.mix
    vocab, seq, batch = cfg["model"]["vocab_size"], job["seq"], job["batch"]
    rows = lambda i: traffic.train_row(seed, i, vocab, seq)   # noqa: E731
    n_ref = int(job["reference_steps"])

    with setup.phase("build"):
        tr = system.Trainer(cell, seed, rows)
        if faults and "trainer" in faults:
            faults["trainer"](tr)
    feed = tr.batches()
    got = {"losses": []}
    with setup.phase("compile_and_first_steps"):
        for s in range(n_ref):
            got["losses"].append(float(tr.step(next(feed))))
            if s == 0:
                got["grad"] = tr.first_grad_norms()
                got["sketch"] = tr.first_grad_sketches()
        got["update"] = tr.update_norms()
    compiles0 = tr.num_compiles()
    setup.close()

    # ---- the window: dispatch one step ahead of the loss being fetched,
    # as a training loop that logs its loss does
    t_open = time.perf_counter()
    t_end = t_open + seconds
    tracer.plan(t_open, seconds, job.get("trace"))
    steps, pending, last = 0, [], None
    while True:
        tracer.tick()
        with rec.span("feed_wait"):
            b = next(feed)
        with rec.span("train_dispatch"):
            pending.append(tr.step(b))
        steps += 1
        if len(pending) > 1:
            with rec.span("loss_fetch"):
                last = float(pending.pop(0))
        if time.perf_counter() >= t_end:
            break
    with rec.span("loss_fetch"):
        for p in pending:
            last = float(p)              # the value fetch closes the window
    t_close = time.perf_counter()
    tracer.stop()
    window_s = t_close - t_open
    tokens = steps * batch * seq

    live = system.memory_live_bytes()
    peak = system.memory_peak_bytes()
    temp = tr.temp_bytes()
    recompiles = tr.num_compiles() - compiles0
    tr.close()

    # ---- the reference, after the window
    t0 = time.perf_counter()
    want = correct.follow_reference(cell, seed, rows, n_ref)
    losses = want["losses"]
    numbers = correct.compare_training(got, want)
    numbers["recompiles_in_window"] = recompiles
    numbers["last_loss_finite"] = 0.0 if last == last and abs(last) < 1e9 \
        else 1.0
    return {
        "attempted": steps, "failed": 0, "numbers": numbers,
        "reference_s": time.perf_counter() - t0,
        "memory_peak_bytes": peak, "window": (t_open, t_close),
        "facts": {"steps": steps, "tokens": tokens,
                  "window_s": window_s - tracer.cost_s,
                  "temp_bytes": temp, "live_bytes": live, "chips": cell.chips,
                  "losses": got["losses"], "ref_losses": losses},
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens / window_s / cell.chips},
    }
