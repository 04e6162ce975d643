#!/usr/bin/env python
"""Summarize paddle_tpu.monitor telemetry.

Reads one or more monitor JSONL files (``monitor.enable(path)`` output, one
per process in distributed runs — ``run.jsonl``, ``run.proc1.jsonl``, ...) or
flight-recorder dumps (``monitor.dump()`` / crash dumps) and prints
per-metric aggregates plus the recompile timeline — the two questions a
post-mortem starts with: "what was the run doing" and "why did it recompile".

Multiple files merge into ONE rank-tagged report: counters sum across ranks
with a per-rank breakdown, timeline entries carry their rank, and recompile
signatures are correlated across ranks (the same divergent signature on all
ranks points at data skew; on one rank, at a placement bug).

The online fleet stream (``run.fleet.jsonl``, written by rank 0's telemetry
aggregator — monitor/collector.py) is accepted alongside the per-process
files and renders its own section (rounds, stale ranks, peak step skew,
WARN roll-up).

Usage:
    python tools/metrics_summary.py run.jsonl [run.proc1.jsonl ...]
    python tools/metrics_summary.py run.jsonl run.fleet.jsonl
    python tools/metrics_summary.py run.flight.json --events
"""
from __future__ import annotations

import argparse
import json
import re
import sys

# the goodput accounting plane's state timeline, in gauge-sum order — the
# ONE copy of the contract outside paddle_tpu (must match
# monitor/goodput.py GOODPUT_STATES; tools/goodput_report.py imports it)
GOODPUT_STATES = ("productive", "compile", "data_wait", "ckpt", "reshard",
                  "overhead", "idle")


def load_records(path):
    """Returns (event_records, final_metrics_snapshot_or_None)."""
    with open(path) as f:
        text = f.read()
    # flight dump: one JSON object with kind == flight_dump
    try:
        doc = json.loads(text)
        if isinstance(doc, dict) and doc.get("kind") == "flight_dump":
            return list(doc.get("events", [])), doc.get("metrics") or None
        if isinstance(doc, dict):
            return [doc], None
    except json.JSONDecodeError:
        pass
    records = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            records.append(json.loads(line))
        except json.JSONDecodeError:
            continue  # torn tail line from a crashed writer
    return records, None


def _proc_of(path, records):
    """Rank of one sink file: the meta record's proc field, else the
    ``.proc<K>.`` launcher naming convention, else None (caller assigns an
    unused rank — rank-less files must not silently collapse onto an
    existing rank and overwrite its metrics)."""
    for r in records:
        if r.get("kind") == "meta" and "proc" in r:
            return int(r["proc"])
    m = re.search(r"\.proc(\d+)\.", path)
    return int(m.group(1)) if m else None


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def _sig_brief(sig):
    parts = []
    for leaf in sig or []:
        shape = "x".join(str(d) for d in leaf.get("shape", []))
        parts.append(f"({shape}){leaf.get('dtype', '?')}")
    return ", ".join(parts)


def _merge_metrics(per_proc):
    """Merge {proc: snapshot} into one rank-tagged view.

    counters sum (breakdown kept), gauges keep the max (breakdown kept),
    histograms pool count/avg/min/max; p99 conservatively takes the max."""
    merged = {"counters": {}, "gauges": {}, "histograms": {}}
    breakdown = {"counters": {}, "gauges": {}}
    for proc, snap in sorted(per_proc.items()):
        for name, v in (snap.get("counters") or {}).items():
            merged["counters"][name] = merged["counters"].get(name, 0) + v
            breakdown["counters"].setdefault(name, {})[proc] = v
        for name, v in (snap.get("gauges") or {}).items():
            merged["gauges"][name] = max(merged["gauges"].get(name, v), v)
            breakdown["gauges"].setdefault(name, {})[proc] = v
        for name, h in (snap.get("histograms") or {}).items():
            m = merged["histograms"].get(name)
            if m is None:
                merged["histograms"][name] = dict(h)
                continue
            n0, n1 = m.get("count", 0), h.get("count", 0)
            tot = n0 + n1
            if tot:
                m["avg"] = (m.get("avg", 0) * n0 + h.get("avg", 0) * n1) / tot
            m["count"] = tot
            m["min"] = min(m.get("min", 0), h.get("min", 0))
            m["max"] = max(m.get("max", 0), h.get("max", 0))
            # quantiles can't be pooled from summaries; the max across ranks
            # is the conservative (never-understates-latency) merge. Only
            # merge keys that EXIST — fabricating p95=0 for pre-p95 (v1)
            # snapshots would defeat the render layer's degrade-to-p99
            for q in ("p50", "p95", "p99"):
                vals = [d[q] for d in (m, h) if q in d]
                if vals:
                    m[q] = max(vals)
    return merged, breakdown


def _brk(breakdown, kind, name, fmt=lambda v: f"{v:g}"):
    per = breakdown.get(kind, {}).get(name)
    if not per or len(per) < 2:
        return ""
    return "  (" + " ".join(f"p{p}={fmt(v)}" for p, v in sorted(per.items())) \
        + ")"


def summarize(paths, show_events=False, out=sys.stdout):
    all_records = []
    # per-proc final metrics snapshot: dump snapshot if given, else the last
    # embedded counters record of that proc's stream
    proc_metrics = {}
    loaded = [(path,) + load_records(path) for path in paths]
    known = {_proc_of(p, recs) for p, recs, _ in loaded} - {None}
    next_free = 0
    for path, recs, snap in loaded:
        proc = _proc_of(path, recs)
        if proc is None:
            # rank-less file: claim an UNUSED rank (a positional default
            # could collide with another file's explicit rank and silently
            # swallow its metrics); single-file invocations stay rank 0
            if len(loaded) == 1:
                proc = 0
            else:
                while next_free in known:
                    next_free += 1
                proc = next_free
                known.add(proc)
        for r in recs:
            r.setdefault("_proc", proc)
        all_records.extend(recs)
        if snap is not None:
            proc_metrics[proc] = snap
        else:
            for r in recs:
                if r.get("kind") == "counters" and isinstance(
                        r.get("metrics"), dict):
                    proc_metrics[proc] = r["metrics"]
    all_records.sort(key=lambda r: r.get("ts", 0))
    if not all_records:
        print("no records", file=out)
        return 1

    procs = sorted({r["_proc"] for r in all_records})
    multi = len(procs) > 1

    def tag(r):
        return f"[p{r['_proc']}] " if multi else ""

    t0 = all_records[0].get("ts", 0)
    meta = next((r for r in all_records if r.get("kind") == "meta"), {})
    span = all_records[-1].get("ts", t0) - t0
    print(f"== monitor summary ==", file=out)
    if multi:
        print(f"schema v{meta.get('schema', all_records[0].get('v', '?'))}  "
              f"ranks {','.join(str(p) for p in procs)}  "
              f"records {len(all_records)}  span {span:.3f}s", file=out)
    else:
        print(f"schema v{meta.get('schema', all_records[0].get('v', '?'))}  "
              f"pid {meta.get('pid', '?')}  proc {meta.get('proc', 0)}  "
              f"records {len(all_records)}  span {span:.3f}s", file=out)

    by_kind = {}
    for r in all_records:
        by_kind.setdefault(r.get("kind", "?"), []).append(r)
    print("events: " + "  ".join(f"{k}={len(v)}"
                                 for k, v in sorted(by_kind.items())),
          file=out)

    metrics, breakdown = _merge_metrics(proc_metrics)
    if not any(metrics.values()):
        metrics = None
    if metrics:
        counters = metrics.get("counters", {})
        if counters:
            print(f"\n== counters =="
                  + (f" (sum over {len(procs)} ranks)" if multi else ""),
                  file=out)
            for name, v in sorted(counters.items()):
                print(f"  {name:<44}{v:>12}"
                      + _brk(breakdown, "counters", name), file=out)
        gauges = metrics.get("gauges", {})
        if gauges:
            print(f"\n== gauges =="
                  + (f" (max over {len(procs)} ranks)" if multi else ""),
                  file=out)
            for name, v in sorted(gauges.items()):
                is_b = name.endswith("_bytes")
                shown = _fmt_bytes(v) if is_b else f"{v:g}"
                print(f"  {name:<44}{shown:>12}"
                      + _brk(breakdown, "gauges", name,
                             _fmt_bytes if is_b else (lambda x: f"{x:g}")),
                      file=out)
        hists = metrics.get("histograms", {})
        if hists:
            print("\n== histograms ==", file=out)
            print(f"  {'name':<34}{'count':>8}{'avg':>12}{'p50':>12}"
                  f"{'p95':>12}{'p99':>12}{'max':>12}", file=out)
            for name, h in sorted(hists.items()):
                # pre-p95 snapshots (schema v1 before this tool's upgrade)
                # degrade to the p99 column value rather than a fake 0
                p95 = h.get("p95", h.get("p99", 0))
                print(f"  {name:<34}{h.get('count', 0):>8}"
                      f"{h.get('avg', 0):>12.6f}{h.get('p50', 0):>12.6f}"
                      f"{p95:>12.6f}{h.get('p99', 0):>12.6f}"
                      f"{h.get('max', 0):>12.6f}",
                      file=out)

    gauges_m = (metrics or {}).get("gauges", {})

    # steps the host held up (monitor/trace.py::stall): one line a record,
    # with what the README's reading table needs to tell the causes apart
    for r in by_kind.get("host_stall", []):
        print(f"\nWARNING: host stall of {r.get('excess_s', 0):.3f}s in "
              f"{r.get('site', '?')} ({r.get('wall_s', 0):.3f}s where "
              f"{r.get('expected_s', 0):.3f}s was expected): run-queue "
              f"wait {r.get('runq_wait_s')}s, process CPU "
              f"{r.get('cpu_process_s')}s, CPU pressure "
              f"{r.get('pressure_cpu_s')}s, major faults {r.get('majflt')}, "
              f"gc {r.get('gc_s')}s, compile {r.get('compile_s')}s",
              file=out)

    # goodput accounting plane (monitor/goodput.py): the gap-free state
    # timeline + MFU/HFU. tools/goodput_report.py is the full per-rank
    # view; this section is the one-look health check + the two WARNs.
    # Multi-rank: states SUM across ranks (a pod timeline) and the
    # headline fraction follows the pod-min doctrine (the pod moves at
    # its slowest rank's pace) — the generic max-merge above would report
    # the BEST rank's fraction and a breakdown belonging to no rank.
    _GOODPUT_STATES = GOODPUT_STATES
    gp_wall = gauges_m.get("goodput/wall_s", 0)
    if gp_wall:
        brk_g = breakdown.get("gauges", {})

        def per_rank(name):
            per = brk_g.get(name)
            return per if per else {0: gauges_m.get(name, 0.0)}

        walls = per_rank("goodput/wall_s")
        pod_wall = sum(walls.values())
        classified_by_rank = {p: 0.0 for p in walls}
        print(f"\n== goodput =="
              + (f" (sum over {len(walls)} ranks)"
                 if len(walls) > 1 else ""), file=out)
        for s in _GOODPUT_STATES:
            per = per_rank(f"goodput/{s}_s")
            v = sum(per.values())
            for p, pv in per.items():
                classified_by_rank[p] = classified_by_rank.get(p, 0.0) + pv
            if v or s in ("productive", "idle"):
                print(f"  {s:<11}{v:>10.3f}s  "
                      f"{v / pod_wall * 100 if pod_wall else 0:>5.1f}%"
                      + _brk(breakdown, "gauges", f"goodput/{s}_s",
                             lambda x: f"{x:.2f}s"), file=out)
        fracs = per_rank("goodput/fraction")
        if len(fracs) > 1:
            worst = min(fracs, key=fracs.get)
            print(f"  pod goodput {fracs[worst]:.1%} (min over ranks — "
                  f"rank {worst} is the floor) over {pod_wall:.3f}s "
                  f"summed wall"
                  + _brk(breakdown, "gauges", "goodput/fraction",
                         lambda x: f"{x:.1%}"), file=out)
        else:
            print(f"  goodput fraction "
                  f"{next(iter(fracs.values()), 0):.1%} over "
                  f"{pod_wall:.3f}s wall", file=out)
        # lost-accounting signature: the per-state gauges are refreshed on
        # every publish/snapshot, so each rank's classified sum tracks that
        # RANK's own record span (not the merged global one — a rank whose
        # monitor session started later, e.g. an elastic restart, is
        # healthy at a shorter span); a rank well short of its span means
        # its ledger stopped being fed/refreshed and the breakdown above
        # is a partial view
        rank_span = {}
        for r in all_records:
            ts = r.get("ts")
            if ts is None:
                continue
            lo, hi = rank_span.get(r["_proc"], (ts, ts))
            rank_span[r["_proc"]] = (min(lo, ts), max(hi, ts))
        for p, classified in sorted(classified_by_rank.items()):
            lo, hi = rank_span.get(p, (0.0, 0.0))
            span_p = hi - lo
            if span_p > 1.0 and classified < 0.95 * span_p:
                tag_r = f"rank {p}: " if len(walls) > 1 else ""
                print(f"  WARNING: {tag_r}classified time "
                      f"{classified:.1f}s covers only "
                      f"{classified / span_p:.0%} of the rank's record "
                      f"span {span_p:.1f}s — lost-accounting signature "
                      f"(the goodput ledger went stale mid-run; gauges "
                      f"above are a partial view)", file=out)
        mfu = gauges_m.get("mfu/mfu")
        hfu = gauges_m.get("mfu/hfu")
        if mfu is not None and hfu is not None:
            print(f"  MFU {mfu:.3f}  HFU {hfu:.3f}"
                  + ("  (recompute replays on the hot path)"
                     if hfu > mfu * 1.01 else ""), file=out)
            # the hardware executes AT LEAST the model's FLOPs; a model
            # utilization above hardware utilization is arithmetic that
            # cannot happen — an accounting bug, not a measurement
            if mfu > hfu * (1 + 1e-9):
                print(f"  WARNING: MFU {mfu:.4f} > HFU {hfu:.4f} — "
                      f"impossible inversion (model FLOPs cannot exceed "
                      f"hardware FLOPs); the FLOP ledger is misattributing "
                      f"(accounting bug)", file=out)
        if gauges_m.get("serve/model_flops_per_token"):
            print(f"  serving: "
                  f"{gauges_m['serve/model_flops_per_token'] / 1e6:.2f}MF"
                  f"/token  "
                  f"{gauges_m.get('serve/tokens_per_s_chip', 0):.1f} "
                  f"tokens/s/chip", file=out)

    world = gauges_m.get("shard/world_size", 0)
    if world > 1:
        accum = gauges_m.get("shard/accum_bytes", 0)
        ideal = gauges_m.get("shard/accum_ideal_bytes", 0)
        print(f"\n== zero sharding ==", file=out)
        print(f"  world {int(world)}  "
              f"grad buckets {int(gauges_m.get('shard/grad_buckets', 0))}",
              file=out)
        if ideal:
            print(f"  grad accumulators {_fmt_bytes(accum)}  "
                  f"(shard ideal {_fmt_bytes(ideal)}, "
                  f"{accum / ideal:.2f}x)", file=out)
            # the regression this section exists to catch: an accumulator
            # that is NOT 1/world_size-sized means the reduce-scatter fell
            # out of the accumulation scan and every device is carrying
            # full-size fp32 grads again
            if accum > 1.15 * ideal:
                print(f"  WARNING: accumulator is {accum / ideal:.2f}x the "
                      f"shard ideal — probable lost sharding constraint "
                      f"(reduce-scatter no longer inside the accumulation "
                      f"scan)", file=out)
        opt_b = gauges_m.get("shard/opt_state_bytes", 0)
        if opt_b:
            print(f"  opt state (per device) {_fmt_bytes(opt_b)}", file=out)

    counters_all = (metrics or {}).get("counters", {})
    reshard_events = by_kind.get("reshard", [])
    if reshard_events or counters_all.get("reshard/loads", 0):
        src = int(gauges_m.get("reshard/src_world", 0))
        dst = int(gauges_m.get("reshard/dst_world", 0))
        ident = int(gauges_m.get("reshard/arrays_identity", 0))
        mapped = int(gauges_m.get("reshard/arrays_mapped", 0))
        gath = int(gauges_m.get("reshard/arrays_gathered", 0))
        moved = gauges_m.get("reshard/bytes_read", 0)
        hists_r = (metrics or {}).get("histograms", {})
        load_s = hists_r.get("reshard/load_s", {})
        print(f"\n== reshard ==", file=out)
        print(f"  world {src} -> {dst}  "
              f"loads {int(counters_all.get('reshard/loads', 0))}  "
              f"arrays {int(gauges_m.get('reshard/arrays', 0))} "
              f"(identity {ident}, index-mapped {mapped}, gathered {gath})",
              file=out)
        print(f"  bytes read {_fmt_bytes(moved)}  "
              f"load wall {load_s.get('max', 0):.3f}s max", file=out)
        # the regression this section exists to catch: a nestable N->M
        # resume (N%M==0 or M%N==0) should be served by index-mapped reads;
        # a gather there means an array's sharded dim moved between worlds
        # and the load materialized the full array on host anyway
        fallbacks = counters_all.get("reshard/nestable_gather_fallbacks", 0)
        if fallbacks:
            print(f"  WARNING: {int(fallbacks)} array(s) of a NESTABLE "
                  f"{src}->{dst} load fell back to gather-then-re-place — "
                  f"the sharded dim moved between world sizes (spec drift), "
                  f"so the load paid a full-size host buffer instead of "
                  f"index-mapped shard reads", file=out)

    remat_events = by_kind.get("remat", [])
    remat_on = gauges_m.get("remat/requested", 0) or remat_events or \
        gauges_m.get("remat/regions", 0)
    if remat_on:
        regions = int(gauges_m.get("remat/regions", 0))
        named = gauges_m.get("remat/saved_name_bytes", 0)
        policy = next((r.get("policy") for r in reversed(remat_events)
                       if r.get("policy")), None)
        print(f"\n== recompute ==", file=out)
        print(f"  policy {policy or '?'}  checkpoint regions {regions}  "
              f"saved named activations {_fmt_bytes(named)}", file=out)
        base = gauges_m.get("remat/baseline_total_bytes", 0)
        if base:
            saved = gauges_m.get("remat/saved_residual_bytes", 0)
            print(f"  measured vs no-remat twin: baseline "
                  f"{_fmt_bytes(base)}, saved residuals {_fmt_bytes(saved)} "
                  f"({saved / base:.0%} of peak)", file=out)
        # the regression this section exists to catch (the pre-wiring state
        # of the repo: fleet/recompute.py existed but nothing routed through
        # it): recompute is REQUESTED but the trace checkpointed nothing —
        # the run silently trains at no-remat memory
        if gauges_m.get("remat/requested", 0) and regions == 0:
            print("  WARNING: recompute is on but zero checkpoint regions "
                  "were applied at trace time — lost-checkpoint signature "
                  "(model blocks not routed through fleet.recompute / scan "
                  "remat; saved-residual bytes are ~0)", file=out)
        elif policy == "selective" and regions > 0 and not named:
            print("  WARNING: selective recompute applied but zero named "
                  "activations were tagged — checkpoint names lost (flash/"
                  "attention path not tagging attn_*/mlp_hidden), so the "
                  "policy saves nothing and backward recomputes everything",
                  file=out)

    counters_m = (metrics or {}).get("counters", {})
    hists_m = (metrics or {}).get("histograms", {})
    serves = by_kind.get("serve_engine", [])
    if serves or any(k.startswith("serve/") for k in counters_m):
        print(f"\n== serving ==", file=out)
        eng = serves[-1] if serves else {}
        if eng:
            q = f"  quantize={eng['quantize']}" if eng.get("quantize") else ""
            if eng.get("kv_blocks"):
                chunk = eng.get("prefill_chunk")
                pre = (f"chunked prefill ({int(chunk)} tok/iter)" if chunk
                       else f"prefill buckets {eng.get('prefill_buckets')}")
                print(f"  engine: {int(eng.get('max_slots', 0))} slots x "
                      f"{int(eng.get('max_len', 0))} positions  paged "
                      f"{int(eng['kv_blocks'])} blocks x "
                      f"{int(eng.get('block_size', 0))} tok  {pre}{q}",
                      file=out)
            else:
                print(f"  engine: {int(eng.get('max_slots', 0))} slots x "
                      f"{int(eng.get('max_len', 0))} positions  prefill "
                      f"buckets {eng.get('prefill_buckets')}{q}", file=out)
        reqs = counters_m.get("serve/requests", 0)
        comps = counters_m.get("serve/completions", 0)
        rej = counters_m.get("serve/rejected", 0)
        # serve/tokens sums live slots per decode step; admissions add the
        # per-request first token the prefill emits
        toks = counters_m.get("serve/tokens", 0) \
            + counters_m.get("serve/admissions", 0)
        serve_ts = [r["ts"] for r in all_records
                    if r.get("kind") in ("serve_admit", "serve_done")]
        span_s = (max(serve_ts) - min(serve_ts)) if len(serve_ts) > 1 else 0.0
        line = f"  requests {int(reqs)}  completed {int(comps)}  " \
               f"rejected {int(rej)}  tokens {int(toks)}"
        if span_s > 0:
            line += f"  ({comps / span_s:.1f} req/s, " \
                    f"{toks / span_s:.1f} tok/s)"
        print(line, file=out)
        for label, h in (("ttft", hists_m.get("serve/ttft_s")),
                         ("queue", hists_m.get("serve/queue_wait_s")),
                         ("prefill", hists_m.get("serve/prefill_s")),
                         ("per-token", hists_m.get("serve/step_s"))):
            if h and h.get("count"):
                print(f"  {label:<9} avg {h['avg'] * 1e3:8.2f}ms  "
                      f"min {h['min'] * 1e3:8.2f}ms  "
                      f"max {h['max'] * 1e3:8.2f}ms  "
                      f"p99 {h['p99'] * 1e3:8.2f}ms  (n={h['count']})",
                      file=out)
        # paged pool health: occupancy / sharing / preemption pressure, and
        # the fragmentation alarm — an admission refused while free blocks
        # covered the slot's need is an ALLOCATOR bug, not saturation
        if gauges_m.get("serve/kv_blocks", 0):
            occ = gauges_m.get("serve/page_occupancy", 0)
            share = gauges_m.get("serve/sharing_ratio", 0)
            print(f"  pages: occupancy {occ:.0%}  kv util "
                  f"{gauges_m.get('serve/kv_util', 0):.0%}  sharing ratio "
                  f"{share:.2f}x  shared blocks "
                  f"{int(gauges_m.get('serve/blocks_shared', 0))}  cow "
                  f"copies {int(gauges_m.get('serve/cow_copies', 0))}  "
                  f"preemptions "
                  f"{int(counters_m.get('serve/preemptions', 0))}",
                  file=out)
            # persistent prefix cache: cross-request hit rate + LRU
            # occupancy (parked refcount-0 blocks waiting for the next
            # same-prefix request)
            hits = gauges_m.get("serve/prefix_hits", 0)
            adm = counters_m.get("serve/admissions", 0)
            lru = gauges_m.get("serve/lru_blocks", 0)
            repeats = gauges_m.get("serve/prefix_repeats", 0)
            total_blocks = gauges_m.get("serve/kv_blocks", 1) - 1
            if hits or lru or repeats:
                rate = hits / adm if adm else 0.0
                print(f"  prefix cache: hits {int(hits)}/{int(adm)} "
                      f"admissions ({rate:.0%})  hit tokens "
                      f"{int(gauges_m.get('serve/prefix_hit_tokens', 0))}  "
                      f"lru {int(lru)}/{int(total_blocks)} blocks "
                      f"({lru / total_blocks if total_blocks else 0:.0%})",
                      file=out)
            # adoption-path-bug signature (mirror of the free>=needed WARN
            # below): prompts with REPEATED prefixes arrived, parked blocks
            # are sitting in the LRU, and yet no admission ever adopted a
            # block — live-shared or parked. Real saturation cannot produce
            # this shape; a broken share_prefix/registry walk can.
            if repeats and lru and not hits \
                    and not gauges_m.get("serve/shared_hits", 0):
                print(f"  WARNING: {int(repeats)} admission(s) repeated an "
                      f"already-registered prefix and {int(lru)} parked "
                      f"block(s) sit in the LRU, but the prefix-cache hit "
                      f"rate is 0% — adoption-path bug signature (the "
                      f"share_prefix walk is not matching what "
                      f"register_prompt published)", file=out)
            # cross-process prefix-cache tier (serving/kvpool.py): the
            # export/fetch/adopt ledger, plus the cold-start signature —
            # a pool that others populated, fetched repeatedly, and never
            # once hit means the digest/generation/geometry handshake is
            # broken (real cold starts MISS once then adopt)
            pool_exports = gauges_m.get("pool/exports", 0)
            pool_fetches = gauges_m.get("pool/fetches", 0)
            if pool_exports or pool_fetches \
                    or gauges_m.get("pool/pending_exports", 0):
                pool_hits_n = gauges_m.get("pool/fetch_hits", 0)
                pool_miss = gauges_m.get("pool/fetch_misses", 0)
                print(f"  kv pool: gen {int(gauges_m.get('pool/gen', 0))}  "
                      f"exports {int(pool_exports)} "
                      f"(errors {int(gauges_m.get('pool/export_errors', 0))})"
                      f"  fetches {int(pool_fetches)} "
                      f"(hits {int(pool_hits_n)}, misses {int(pool_miss)})  "
                      f"adopted {int(gauges_m.get('pool/adopted_blocks', 0))}"
                      f" blocks / "
                      f"{int(gauges_m.get('pool/adopted_tokens', 0))} tokens"
                      f"  pending "
                      f"{int(gauges_m.get('pool/pending_exports', 0))}",
                      file=out)
                if pool_exports and pool_fetches >= 2 and not pool_hits_n:
                    print(f"  WARNING: the kv pool holds "
                          f"{int(pool_exports)} exported block(s) and "
                          f"{int(pool_fetches)} fetch(es) ran, yet ZERO "
                          f"adopted — cold-start-never-adopts signature "
                          f"(digest, generation or geometry mismatch "
                          f"between exporter and fetcher; a restarted "
                          f"engine is re-prefilling prompts the pool "
                          f"already holds)", file=out)
            tp = gauges_m.get("serve/tp", 0)
            if tp and tp > 1:
                # the engine shards the pool's head axis when it divides,
                # head_dim for GQA fallback, replicated otherwise — this
                # line only knows the degree, so it stays layout-neutral
                print(f"  tensor-parallel decode: tp={int(tp)} (KV pool "
                      f"sharded over the mesh; table/cursors replicated)",
                      file=out)
            overload = counters_m.get("serve/rejected_overload", 0)
            if overload:
                print(f"  queue overload rejections {int(overload)} "
                      f"(admission queue saturated — callers should back "
                      f"off or the pool should grow)", file=out)
        # speculative decoding: drafted-vs-accepted economics per drafter,
        # and the wasted-work alarm — spec enabled with acceptance ~0 means
        # every verify dispatch carried dead drafts (a misconfigured
        # drafter burns chunk-shaped dispatches for nothing)
        spec_steps = counters_m.get("serve/spec_steps", 0)
        if spec_steps:
            drafted = counters_m.get("serve/spec_drafted", 0)
            accepted = counters_m.get("serve/spec_accepted", 0)
            aps = gauges_m.get("serve/spec_accepted_per_step", 0)
            rate = accepted / drafted if drafted else 0.0
            print(f"  speculation: {int(spec_steps)} verify steps  "
                  f"drafted {int(drafted)}  accepted {int(accepted)} "
                  f"({rate:.0%})  accepted/step {aps:.2f}", file=out)
            per = {}
            for k, v in counters_m.items():
                if k.startswith("serve/spec_drafted."):
                    per.setdefault(k.split(".", 1)[1], [0, 0])[0] = v
                elif k.startswith("serve/spec_accepted."):
                    per.setdefault(k.split(".", 1)[1], [0, 0])[1] = v
            for name in sorted(per):
                d, acc = per[name]
                print(f"    drafter {name}: drafted {int(d)}  accepted "
                      f"{int(acc)} "
                      f"({acc / d if d else 0.0:.0%})", file=out)
            if drafted >= 16 and rate < 0.05:
                print(f"  WARNING: speculation is on but the draft "
                      f"acceptance rate is {rate:.1%} over {int(drafted)} "
                      f"drafted tokens — wasted-work signature (every "
                      f"verify dispatch pays for drafts that never land; "
                      f"switch drafters or turn speculation off)", file=out)
        # guardrail plane (deadlines / cancellation / drain / watchdog):
        # every request ends in a terminal status, and this block accounts
        # for the non-"done" ones next to the completions above
        expired = counters_m.get("serve/expired", 0)
        cancelled = counters_m.get("serve/cancelled", 0)
        drains = counters_m.get("serve/drained", 0)
        drain_rej = counters_m.get("serve/rejected_draining", 0)
        hangs = counters_m.get("serve/hang_warns", 0)
        if expired or cancelled or drains or drain_rej or hangs:
            print(f"  guardrails: expired {int(expired)}  cancelled "
                  f"{int(cancelled)}  drains {int(drains)}  "
                  f"rejected_draining {int(drain_rej)}  hang warns "
                  f"{int(hangs)}", file=out)
            # pool-thrash signature: expirations clustering with
            # preemptions — a request that was evicted (compute redone on
            # re-admission) and THEN blew its deadline lost the budget to
            # pool pressure, not to its own length
            thrash = [r for r in by_kind.get("serve_expire", [])
                      if r.get("preemptions", 0) > 0]
            if thrash:
                print(f"  WARNING: {len(thrash)} expired request(s) had "
                      f"been preempted first — pool-thrash signature "
                      f"(eviction/recompute churn is eating deadline "
                      f"budget; raise kv_blocks or lower deadlines)",
                      file=out)
        for r in by_kind.get("serve_hang", []):
            print(f"  WARNING: {tag(r)}dispatch hang: {r.get('path', '?')} "
                  f"executable exceeded PADDLE_SERVE_HANG_S="
                  f"{r.get('hang_s')}s ({r.get('elapsed_s', 0):.2f}s when "
                  f"caught)"
                  + (f"  traces {r['traces'][:3]}" if r.get("traces")
                     else ""), file=out)
        # pool-adoption carve-out: a reject tagged pool_blocks > 0 adopted
        # that many blocks from the cross-process tier mid-admission, so
        # its free-vs-needed figures straddle the splice — legitimate, not
        # the allocator-bug shape this WARN patrols for
        frag = [r for r in by_kind.get("serve_page_reject", [])
                if r.get("free_blocks", 0) >= r.get("needed_blocks", 1)
                and not r.get("pool_blocks")]
        if frag:
            worst = max(frag, key=lambda r: r.get("free_blocks", 0))
            print(f"  WARNING: {len(frag)} paged admission(s) rejected "
                  f"with free blocks >= the slot's need (e.g. free "
                  f"{int(worst['free_blocks'])} vs needed "
                  f"{int(worst['needed_blocks'])}) — allocator "
                  f"fragmentation/logic bug, not pool saturation",
                  file=out)
        steps_n = counters_m.get("serve/decode_steps", 0)
        slots_max = max((int(e.get("max_slots", 0)) for e in serves),
                        default=int(eng.get("max_slots", 0) or 0))
        if steps_n and slots_max:
            # several engines can share one sink; dividing by the LARGEST
            # slot count keeps this a lower bound instead of a >100% figure
            occ = counters_m.get("serve/tokens", 0) / (steps_n * slots_max)
            multi = (f" across {len(serves)} engines"
                     if len(serves) > 1 else "")
            print(f"  slot occupancy {occ:.0%} over {int(steps_n)} "
                  f"decode steps{multi}", file=out)
        mints = by_kind.get("serve_compile", [])
        if mints:
            # the serving analog of the train-side recompile sentinel: a
            # decode step's shape is fixed by construction, so a SECOND
            # decode mint FROM THE SAME ENGINE means slot churn leaked into
            # shapes somewhere. Sinks can hold several engines (int8 next
            # to fp32, one per model) — each gets its own first mint free.
            decode_by_eng = {}
            for r in mints:
                if r.get("path") == "decode":
                    decode_by_eng.setdefault(
                        (r.get("_proc"), r.get("engine")), []).append(r)
            remints = [r for rs in decode_by_eng.values()
                       for r in sorted(rs, key=lambda x: x.get("ts", 0))[1:]]
            remint_ids = {id(r) for r in remints}
            print(f"  executables ({len(mints)}):", file=out)
            for r in mints:
                b = f"[{r.get('bucket')}]" if r.get("bucket") else ""
                e = f" eng{r['engine']}" if r.get("engine") is not None else ""
                late = "  REMINT" if id(r) in remint_ids else ""
                print(f"  +{r.get('ts', t0) - t0:9.3f}s  "
                      f"{tag(r)}{r.get('path', '?')}{b}{e} "
                      f"compile {r.get('compile_s', 0):.3f}s{late}", file=out)
            if remints:
                print(f"  WARNING: decode executable re-minted "
                      f"{len(remints)}x — the zero-recompile steady-state "
                      f"contract is broken (a shape depends on the "
                      f"live-slot set)", file=out)

    # fleet router (serving/router.py): placement mix, failover activity,
    # and the requeue-storm signature — requeues climbing while the router
    # never ejected anything means requests are BOUNCING between live
    # engines (flapping transport / drain loop / chaos drops), not failing
    # over from a dead one
    route_counters = {k: v for k, v in counters_m.items()
                      if k.startswith("route/")}
    route_states = by_kind.get("route_state", [])
    if route_counters or route_states:
        print(f"\n== router ==", file=out)
        aff = route_counters.get("route/affinity_hits", 0)
        spills = route_counters.get("route/spills", 0)
        placed = aff + spills
        requeues = route_counters.get("route/requeues", 0)
        ejections = route_counters.get("route/ejections", 0)
        rejected = route_counters.get("route/rejected", 0)
        queued = route_counters.get("route/queued", 0)
        line = (f"  placed {int(placed)}  affinity {int(aff)}"
                + (f" ({aff / placed:.0%})" if placed else "")
                + f"  spills {int(spills)}  requeues {int(requeues)}  "
                f"ejections {int(ejections)}  rejected {int(rejected)}")
        if queued:
            line += (f"  queued {int(queued)} (depth "
                     f"{int(gauges_m.get('route/queue_depth', 0))})")
        print(line, file=out)
        if route_states:
            doors = route_states[-1].get("doors") or {}
            for name in sorted(doors):
                door = doors[name]
                line = (f"  engine {name}: {door.get('state', '?'):<10} "
                        f"queue {int(door.get('queue_depth', 0))}  active "
                        f"{int(door.get('active', 0))}  free_slots "
                        f"{int(door.get('free_slots', 0))}  prefix_hits "
                        f"{int(door.get('prefix_hits', 0))}")
                if door.get("pool_gen") is not None:
                    line += (f"  pool_hits "
                             f"{int(door.get('pool_hits') or 0)} "
                             f"(gen {int(door.get('pool_gen'))})")
                print(line, file=out)
        ejs = by_kind.get("route_eject", [])
        for r in ejs:
            print(f"  +{r.get('ts', t0) - t0:9.3f}s  {tag(r)}ejected "
                  f"{r.get('engine', '?')}: {r.get('why', '?')}", file=out)
        reqs_by_why = {}
        for r in by_kind.get("route_requeue", []):
            reqs_by_why.setdefault(r.get("why", "?"), []).append(r)
        for why, rs in sorted(reqs_by_why.items()):
            print(f"  requeues[{why}] x{len(rs)} (e.g. "
                  f"{rs[-1].get('request', '?')}: "
                  f"{rs[-1].get('src', '?')} -> {rs[-1].get('dst', '?')})",
                  file=out)
        if requeues >= 3 and not ejections:
            print(f"  WARNING: {int(requeues)} requeue(s) with ZERO "
                  f"ejections — requeue-storm signature (requests bounce "
                  f"between live engines instead of failing over from a "
                  f"dead one: flapping transport, a drain/uncordon loop, "
                  f"or injected chaos drops; nothing actually died)",
                  file=out)

    # model-health plane (monitor/health.py): the numerics post-mortem next
    # to the time/throughput ones above — trip timeline, per-layer tensor
    # stats, divergence flags, and the two signatures worth shouting about
    health_kinds = ("health_nan", "health_overflow", "health_spike",
                    "health_rollback", "health_fault", "serve_nan_logits")
    health_events = [r for k in health_kinds for r in by_kind.get(k, [])]
    health_on = health_events or any(
        k.startswith("health/") for k in list(counters_m) + list(gauges_m))
    if health_on:
        health_events.sort(key=lambda r: r.get("ts", 0))
        nan_trips = int(counters_m.get("health/nan_trips", 0))
        print(f"\n== health ==", file=out)
        print(f"  nan trips {nan_trips}  overflow trips "
              f"{int(counters_m.get('health/overflow_trips', 0))}  spikes "
              f"{int(counters_m.get('health/spikes', 0))}  rollbacks "
              f"{int(counters_m.get('health/rollbacks', 0))}  found_inf "
              f"{int(counters_m.get('health/found_inf', 0))}  nan logits "
              f"{int(counters_m.get('serve/nan_logits', 0))}", file=out)
        if health_events:
            shown = health_events[:24]
            print(f"  trip timeline ({len(health_events)}):", file=out)
            for r in shown:
                dt = r.get("ts", t0) - t0
                kind = r.get("kind")
                if kind == "health_nan":
                    where = ", ".join(r.get("groups") or []) or "forward loss"
                    leaves = [b.get("leaf") for b in r.get("leaves") or []]
                    detail = f"non-finite in [{where}]" \
                        + (f"  leaves {leaves}" if leaves else "")
                elif kind == "health_overflow":
                    detail = (f"|grad| {r.get('max_abs', 0):.3e} > "
                              f"{r.get('threshold', 0):.1e} in "
                              f"[{', '.join(r.get('groups') or [])}]")
                elif kind == "health_spike":
                    med = r.get("median")
                    detail = ((f"loss {r.get('loss'):.6g} vs median "
                               f"{med:.6g}") if med is not None
                              else "non-finite loss") \
                        + f" ({r.get('source', '?')})"
                elif kind == "health_rollback":
                    detail = (f"rolled back to step "
                              f"{r.get('restored_step')} after spike at "
                              f"step {r.get('spike_step')}")
                elif kind == "health_fault":
                    detail = (f"chaos fault {r.get('action')} on "
                              f"{r.get('leaf')} (call {r.get('call')})")
                else:
                    detail = (f"non-finite logits in "
                              f"{r.get('where', '?')} — request failed")
                step = f" step {r['step']}" if r.get("step") is not None \
                    else ""
                tr_id = f"  [trace {r['trace']}]" if r.get("trace") else ""
                print(f"  +{dt:9.3f}s  {tag(r)}{kind}{step}: "
                      f"{detail}{tr_id}", file=out)
            if len(health_events) > len(shown):
                print(f"  ... {len(health_events) - len(shown)} more "
                      f"(use --events)", file=out)
        layer_stats = {}
        for k, v in gauges_m.items():
            for fam, col in (("health/grad_norm.", 0),
                             ("health/grad_max.", 1),
                             ("health/update_ratio.", 2)):
                if k.startswith(fam):
                    layer_stats.setdefault(k[len(fam):], [0.0] * 3)[col] = v
        if layer_stats:
            print(f"  {'layer group':<32}{'grad_norm':>12}{'grad_max':>12}"
                  f"{'upd/w':>12}", file=out)
            for gname in sorted(layer_stats):
                gn, gm, ur = layer_stats[gname]
                print(f"  {gname:<32}{gn:>12.4g}{gm:>12.4g}{ur:>12.3g}",
                      file=out)
        acts = {k[len("health/act_rms."):]: v for k, v in gauges_m.items()
                if k.startswith("health/act_rms.")}
        if acts:
            print("  act rms: " + "  ".join(
                f"{n}={v:.4g}" for n, v in sorted(acts.items())), file=out)
        div_warns = [w for w in by_kind.get("fleet_warn", [])
                     if w.get("warn") == "weight_divergence"]
        if gauges_m.get("fleet/weight_divergence", 0) or div_warns:
            ranks_div = sorted({w.get("rank") for w in div_warns
                                if w.get("rank") is not None})
            print(f"  weight divergence: FLAGGED"
                  + (f" — rank(s) {ranks_div}" if ranks_div else "")
                  + (f" [trace {div_warns[-1]['trace']}]"
                     if div_warns and div_warns[-1].get("trace") else ""),
                  file=out)
            # a resumed/elastic rank can legitimately lag a few steps; a
            # fork with NO restart churn anywhere in the record cannot
            restarts = [r for r in by_kind.get("fleet_rank", [])
                        if (r.get("inc") or {}).get("gen", 0)]
            if not restarts and not by_kind.get("elastic_scale", []):
                print("  WARNING: a rank's weight digest forked with ZERO "
                      "elastic/restart events in the record — not "
                      "explainable as a stale resume; treat as silent "
                      "corruption or a desynced optimizer on that rank",
                      file=out)
        # the scaler-protection cross-check: a NaN trip while the scaler
        # skipped nothing means the poisoned grads reached the weights
        if nan_trips and not counters_m.get("train_step/skipped_updates", 0):
            print(f"  WARNING: {nan_trips} non-finite trip(s) with ZERO "
                  f"scaler-skipped updates — the tripped step's update was "
                  f"NOT protected (no GradScaler in the loop, or it never "
                  f"saw these grads); assume the weights already carry the "
                  f"NaN and roll back", file=out)

    # fleet stream (run.fleet.jsonl — monitor/collector.py's online
    # aggregation): the same tool reads the live plane's output post-mortem
    fleet_recs = by_kind.get("fleet", [])
    fleet_meta = (by_kind.get("fleet_meta") or [{}])[-1]
    fleet_warns = by_kind.get("fleet_warn", [])
    if fleet_recs or fleet_warns:
        print(f"\n== fleet (online aggregation) ==", file=out)
        last = fleet_recs[-1] if fleet_recs else {}
        d = last.get("derived") or {}
        print(f"  world {fleet_meta.get('world', '?')}  publish every "
              f"{fleet_meta.get('publish_s', '?')}s  rounds "
              f"{len(fleet_recs)}  ranks seen "
              f"{len(last.get('ranks') or [])}", file=out)
        if last:
            stale = last.get("stale") or []
            # attribute the PEAK skew to the rank of the round that
            # produced it — the final round's slowest rank may be an
            # innocent bystander of a long-recovered episode
            peak = max(fleet_recs, key=lambda f: f.get("derived", {})
                       .get("fleet/step_skew", 1.0))
            pd = peak.get("derived", {})
            line = (f"  final: {len(last.get('live') or [])} live"
                    + (f", {len(stale)} STALE {stale}" if stale else "")
                    + f"  peak step skew "
                    f"{pd.get('fleet/step_skew', 1.0):.2f}x")
            if pd.get("fleet/slowest_rank") is not None:
                line += f" (slowest rank {pd['fleet/slowest_rank']})"
            print(line, file=out)
        if fleet_warns:
            by_warn = {}
            for w in fleet_warns:
                by_warn.setdefault(w.get("warn", "?"), []).append(w)
            print(f"  warnings ({len(fleet_warns)}):", file=out)
            for warn, ws in sorted(by_warn.items()):
                last_w = ws[-1]
                print(f"    {warn} x{len(ws)}: {last_w.get('msg', '')}",
                      file=out)

    recompiles = by_kind.get("recompile", [])
    print(f"\n== recompile timeline ({len(recompiles)}) ==", file=out)
    for r in recompiles:
        dt = r.get("ts", t0) - t0
        cs = r.get("compile_s")
        cs = f"compile {cs:.3f}s" if cs is not None else "compile n/a"
        div = r.get("divergent") or []
        tail = ("divergent: " + "; ".join(div)) if div \
            else ("sig: " + _sig_brief(r.get("sig")))
        print(f"  +{dt:9.3f}s  {tag(r)}[{r.get('path', '?'):>3}] "
              f"#{r.get('count', '?')}  {cs}  {tail}", file=out)
    if multi and recompiles:
        # rank correlation: which ranks minted each signature (ROADMAP
        # "distributed metric aggregation" — same sig everywhere = data
        # skew reaching all ranks; one rank = that rank's placement bug)
        by_sig = {}
        for r in recompiles:
            by_sig.setdefault(_sig_brief(r.get("sig")), set()).add(r["_proc"])
        print("\n== recompile rank correlation ==", file=out)
        for sig, ps in sorted(by_sig.items()):
            where = "all ranks" if set(procs) <= ps else \
                "rank " + ",".join(str(p) for p in sorted(ps))
            print(f"  {where:<16} {sig}", file=out)

    mems = by_kind.get("memory", [])
    if mems:
        print(f"\n== executable memory ({len(mems)} buckets) ==", file=out)
        for r in mems:
            print(f"  {tag(r)}bucket {r.get('bucket', '?')}: "
                  f"args {_fmt_bytes(r.get('argument_bytes', 0))}  "
                  f"out {_fmt_bytes(r.get('output_bytes', 0))}  "
                  f"temp {_fmt_bytes(r.get('temp_bytes', 0))}  "
                  f"total {_fmt_bytes(r.get('total_bytes', 0))}", file=out)

    epochs = by_kind.get("epoch", [])
    if epochs:
        print(f"\n== epochs ({len(epochs)}) ==", file=out)
        for r in epochs:
            logs = r.get("logs") or {}
            logstr = "  ".join(f"{k}={v:.4f}" for k, v in logs.items())
            print(f"  {tag(r)}epoch {r.get('epoch', '?')}: "
                  f"{r.get('steps', '?')} "
                  f"steps  {r.get('wall_s', 0):.3f}s  {logstr}", file=out)

    stalls = by_kind.get("loader_stall", [])
    if stalls:
        total = sum(r.get("wait_s", 0) for r in stalls)
        print(f"\n== loader stalls ==\n  {len(stalls)} stalls, "
              f"{total:.3f}s total blocked", file=out)

    crashes = by_kind.get("crash", [])
    for r in crashes:
        print(f"\n== crash ==\n  {tag(r)}{r.get('exc_type', '?')} -> "
              f"{r.get('dump', '?')}", file=out)

    if show_events:
        print("\n== raw events ==", file=out)
        for r in all_records:
            print(f"  {json.dumps(r)}", file=out)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="monitor JSONL file(s) and/or flight-recorder dumps")
    ap.add_argument("--events", action="store_true",
                    help="also print every raw event record")
    args = ap.parse_args(argv)
    return summarize(args.paths, show_events=args.events)


if __name__ == "__main__":
    sys.exit(main())
