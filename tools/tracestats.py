#!/usr/bin/env python3
"""Summarize (and validate) a serving telemetry trace (DESIGN.md §10).

    PYTHONPATH=src python -m tools.tracestats experiments/trace.jsonl
    PYTHONPATH=src python -m tools.tracestats trace.json --check

Reads either trace format ``engine.dump_trace()`` writes: JSONL (one
record per line: a ``meta`` header, then ``tick``/``span`` events) or
Chrome ``trace_event`` JSON (ticks are reconstructed from the ``cat:
"tick"`` complete events; request lifecycle spans only survive in the
JSONL format, so span-level stats and checks are skipped for Chrome
dumps).  Merged multi-replica dumps (``ReplicaRouter.dump_trace()``:
one meta per replica, every event tagged ``"replica": i``) are split
per replica — the summary gains a fleet rollup and ``--check`` re-runs
every tick/span invariant independently per replica.

The summary reports tick counts, packed vs padded token totals (budget
utilization — the padding-waste view), the host/device wall split,
request percentiles recomputed *exactly* from the lifecycle spans
(TTFT / latency / queue wait), and the preemption timeline.

``--check`` turns the structural invariants into CI gates (exit 1 on
violation):

  * the trace is non-empty and every tick carries every ``TICK_FIELDS``
    field;
  * per-tick ``packed_tokens`` sum exactly to the meta record's running
    counter (skipped when ticks were dropped from the ring);
  * speculative accounting: per tick ``0 <= accepted <= drafted``, and
    on pure-decode ticks ``emitted == decode_tokens - drafted +
    accepted`` (the rejected draft tail is the only packed-vs-emitted
    gap); drafted/accepted sums match the ``spec.*`` running counters;
  * host phases (schema 5): every ``phase_s`` entry is non-negative,
    the phases of a tick sum to no more than the time since the
    previous tick event ended (phases do not overlap, and a tick event
    takes those since the last one), and ``ready_t >= dispatch_t``;
  * KV capacity tiers (DESIGN.md §13): per tick, swapped-in pages never
    exceed the device pool's capacity (``pool_free + pool_cached +
    pool_in_use``), the ``quant`` flag is constant across the trace (pool
    dtype never changes mid-run), and the per-tick ``swap_in``/
    ``swap_out`` sums match the ``swap.*`` running counters;
  * request spans pair up: ``submit`` precedes everything, admits
    balance preempts + a terminal ``finish``, and a request carries at
    most one terminal span (``finish`` or ``cancel`` — a cancelled
    request's admits balance its preempts, plus one open admit when it
    was aborted in a slot); skipped when spans were dropped or the
    engine was still mid-flight at dump time;
  * the histogram's p99 TTFT agrees with the exact span recompute to
    within one geometric bucket (rtol 0.35 — the fixed-bucket
    estimator's documented error bound, see ``repro.obs.metrics``).
"""
from __future__ import annotations

import argparse
import json
import sys

# the span/tick schema the engine writes — import the authoritative
# constants when src/ is importable, else fall back to a frozen copy so
# the tool still runs on a bare checkout of just the trace file
try:
    from repro.obs import SPAN_KINDS, TICK_FIELDS
except ImportError:                                   # pragma: no cover
    SPAN_KINDS = ("submit", "admit", "first_token", "preempt", "swap_out",
                  "swap_in", "finish", "cancel")
    TICK_FIELDS = ("tick", "t", "kind", "wall_s", "host_s", "device_s",
                   "packed_tokens", "padded_tokens", "prefill_tokens",
                   "decode_tokens", "drafted", "accepted", "emitted",
                   "live_slots", "waiting",
                   "pool_free", "pool_cached", "pool_in_use",
                   "prefix_hit_tokens", "preemptions", "cow_copies",
                   "dispatches", "finished", "swap_in", "swap_out",
                   "quant", "phase_s", "dispatch_t", "ready_t", "compiled")


def load(path: str):
    """-> (meta, ticks, spans, fmt).  Chrome dumps yield spans=None."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None                 # multiple lines -> JSONL
    if isinstance(doc, dict) and "traceEvents" in doc:
        meta = doc.get("metadata", {})
        ticks = [dict(e["args"], t=e["ts"] / 1e6)
                 for e in doc.get("traceEvents", [])
                 if e.get("cat") == "tick"]
        ticks.sort(key=lambda t: t["tick"])
        return meta, ticks, None, "chrome"
    metas, ticks, spans = [], [], []
    for line in text.splitlines():
        if not line.strip():
            continue
        rec = json.loads(line)
        kind = rec.get("type")
        if kind == "meta":
            metas.append(rec)
        elif kind == "tick":
            ticks.append(rec)
        elif kind == "span":
            spans.append(rec)
    # a ReplicaRouter dump_trace() merges N engines into one stream: one
    # meta per replica and every event tagged "replica" — surface them
    # all so split_replicas() can re-run the per-engine checks
    if len(metas) > 1 or any("replica" in m for m in metas):
        meta = {"type": "meta", "merged": True,
                "replicas": {m.get("replica", j): m
                             for j, m in enumerate(metas)}}
    else:
        meta = metas[-1] if metas else {}
    ticks.sort(key=lambda t: (t.get("replica", 0), t["tick"]))
    return meta, ticks, spans, "jsonl"


def split_replicas(meta, ticks, spans):
    """Split a merged multi-replica trace into per-replica
    ``(meta, ticks, spans)`` triples keyed by replica index, or None for
    an ordinary single-engine trace."""
    if not meta.get("merged"):
        return None
    out = {}
    for i in sorted(meta["replicas"]):
        tk = [t for t in ticks if t.get("replica") == i]
        sp = None if spans is None \
            else [s for s in spans if s.get("replica") == i]
        out[i] = (meta["replicas"][i], tk, sp)
    return out


def percentile(values, q: float):
    """Exact order statistic (nearest-rank with interpolation)."""
    if not values:
        return None
    vs = sorted(values)
    if len(vs) == 1:
        return vs[0]
    pos = (len(vs) - 1) * q / 100.0
    lo = int(pos)
    frac = pos - lo
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + frac * (vs[hi] - vs[lo])


def span_stats(spans):
    """Per-request lifecycle recompute: exact TTFT / latency / queue-wait
    lists plus the per-request event map (for the pairing check)."""
    per_req = {}
    for s in spans:
        per_req.setdefault(s["req"], []).append(s)
    ttft, latency, qwait = [], [], []
    for evs in per_req.values():
        t = {k: None for k in SPAN_KINDS}
        for s in evs:
            if t[s["kind"]] is None:          # first occurrence only
                t[s["kind"]] = s["t"]
        if t["submit"] is not None and t["first_token"] is not None:
            ttft.append(t["first_token"] - t["submit"])
        if t["submit"] is not None and t["finish"] is not None:
            latency.append(t["finish"] - t["submit"])
        if t["submit"] is not None and t["admit"] is not None:
            qwait.append(t["admit"] - t["submit"])
    return per_req, ttft, latency, qwait


def summarize(meta, ticks, spans) -> dict:
    packed = sum(t["packed_tokens"] for t in ticks)
    padded = sum(t["padded_tokens"] for t in ticks)
    host = sum(t["host_s"] for t in ticks)
    device = sum(t["device_s"] for t in ticks)
    out = {
        "ticks": len(ticks),
        "dropped_ticks": meta.get("dropped_ticks", 0),
        "kinds": sorted({t["kind"] for t in ticks}),
        "packed_tokens": packed,
        "padded_tokens": padded,
        "budget_utilization": round(packed / padded, 4) if padded else None,
        "prefill_tokens": sum(t["prefill_tokens"] for t in ticks),
        "decode_tokens": sum(t["decode_tokens"] for t in ticks),
        "drafted": sum(t.get("drafted", 0) for t in ticks),
        "accepted": sum(t.get("accepted", 0) for t in ticks),
        "emitted": sum(t["emitted"] for t in ticks),
        "host_s": round(host, 6),
        "device_s": round(device, 6),
        "preemptions": sum(t["preemptions"] for t in ticks),
        "preemption_timeline": [
            {"tick": t["tick"], "t": round(t["t"], 6),
             "preemptions": t["preemptions"]}
            for t in ticks if t["preemptions"]],
        "prefix_hit_tokens": sum(t["prefix_hit_tokens"] for t in ticks),
        "cow_copies": sum(t["cow_copies"] for t in ticks),
        "swap_in_pages": sum(t.get("swap_in", 0) for t in ticks),
        "swap_out_pages": sum(t.get("swap_out", 0) for t in ticks),
        "quant": any(t.get("quant") for t in ticks),
    }
    if out["drafted"]:
        out["accept_rate"] = round(out["accepted"] / out["drafted"], 4)
    if spans is not None:
        _, ttft, latency, qwait = span_stats(spans)
        out["requests"] = {
            "submitted": len({s["req"] for s in spans}),
            "finished": sum(1 for s in spans if s["kind"] == "finish"),
        }
        for label, vals in (("ttft_s", ttft), ("latency_s", latency),
                            ("queue_wait_s", qwait)):
            out[label] = None if not vals else {
                "count": len(vals),
                "p50": percentile(vals, 50),
                "p90": percentile(vals, 90),
                "p99": percentile(vals, 99),
                "max": max(vals)}
    return out


def check_phases(ticks) -> list:
    """Schema-5 host phases: non-negative, no more than the time since
    the previous tick event ended, and the sync after the dispatch."""
    errs = []
    prev = None
    for t in ticks:
        phases = t.get("phase_s")
        if phases is None:
            continue
        if any(v < 0 for v in phases.values()):
            errs.append(f"tick {t['tick']}: negative phase in {phases}")
            break
        end = t["t"] + t["wall_s"]
        if prev is not None and prev["tick"] + 1 == t["tick"]:
            since = end - (prev["t"] + prev["wall_s"])
            # 1 ns: float rounding of the epoch-relative times
            if sum(phases.values()) > since + 1e-9:
                errs.append(f"tick {t['tick']}: phases sum to "
                            f"{sum(phases.values()):.6f} s, more than the "
                            f"{since:.6f} s since the previous tick")
                break
        prev = t
        d, r = t.get("dispatch_t"), t.get("ready_t")
        if d is not None and r is not None and r < d:
            errs.append(f"tick {t['tick']}: ready_t {r} before "
                        f"dispatch_t {d}")
            break
    return errs


def check(meta, ticks, spans, summary) -> list:
    """Structural gates; returns the list of violations (empty = pass)."""
    errs = []
    if not ticks:
        errs.append("trace has no tick events")
        return errs
    # older traces predate the capacity-tier (v4) and host-phase (v5)
    # fields; don't fail archives
    schema = meta.get("schema", 0)
    newer = (() if schema >= 5 else
             ("phase_s", "dispatch_t", "ready_t", "compiled")) + (
        () if schema >= 4 else ("swap_in", "swap_out", "quant"))
    required = tuple(f for f in TICK_FIELDS if f not in newer)
    for t in ticks:
        missing = [f for f in required if f not in t]
        if missing:
            errs.append(f"tick {t.get('tick')} missing fields: {missing}")
            break
    # speculative decoding (DESIGN.md §11): a verify can only accept
    # tokens it drafted, and on pure-decode ticks the emitted count is
    # the packed decode tokens minus the rejected draft tail
    # (decode_tokens - drafted + accepted); mixed ticks also emit
    # prefill-completion tokens, so the equality is gated on
    # prefill_tokens == 0
    for t in ticks:
        drafted = t.get("drafted", 0)
        accepted = t.get("accepted", 0)
        if not (0 <= accepted <= drafted):
            errs.append(f"tick {t['tick']}: accepted {accepted} outside "
                        f"[0, drafted={drafted}]")
            break
        if (t.get("prefill_tokens") == 0 and "emitted" in t
                and t["emitted"] !=
                t["decode_tokens"] - drafted + accepted):
            errs.append(f"tick {t['tick']}: emitted {t['emitted']} != "
                        f"decode_tokens {t['decode_tokens']} - drafted "
                        f"{drafted} + accepted {accepted}")
            break
    errs.extend(check_phases(ticks))
    # KV capacity tiers (DESIGN.md §13): a tick cannot stream in more
    # pages than the device pool can hold, and the pool's quantization
    # never changes mid-run
    quants = {bool(t.get("quant", False)) for t in ticks}
    if len(quants) > 1:
        errs.append("quant flag changes across ticks (pool dtype is "
                    "fixed at engine construction)")
    for t in ticks:
        pool = (t.get("pool_free", 0) + t.get("pool_cached", 0)
                + t.get("pool_in_use", 0))
        if pool and t.get("swap_in", 0) > pool:
            errs.append(f"tick {t['tick']}: swap_in {t['swap_in']} pages "
                        f"exceeds device pool capacity {pool}")
            break
    metrics = meta.get("metrics", {})
    if meta.get("dropped_ticks", 0) == 0 and "packed_tokens" in metrics:
        for key in ("packed_tokens", "padded_tokens",
                    "prefill_tokens", "decode_tokens"):
            if summary[key] != metrics[key]:
                errs.append(f"tick {key} sum {summary[key]} != running "
                            f"counter {metrics[key]}")
        for key, field in (("spec.drafted", "drafted"),
                           ("spec.accepted", "accepted"),
                           ("swap.in_pages", "swap_in_pages"),
                           ("swap.out_pages", "swap_out_pages")):
            if key in metrics and summary[field] != metrics[key]:
                errs.append(f"tick {field} sum {summary[field]} != "
                            f"running counter {key} {metrics[key]}")
    if spans is not None:
        for s in spans:
            if s["kind"] not in SPAN_KINDS:
                errs.append(f"unknown span kind {s['kind']!r}")
                break
        if meta.get("dropped_spans", 0) == 0:
            per_req, ttft, _, _ = span_stats(spans)
            for rid, evs in sorted(per_req.items()):
                kinds = [s["kind"] for s in evs]
                if kinds[0] != "submit":
                    errs.append(f"req {rid}: first span is {kinds[0]!r}, "
                                f"not 'submit'")
                admits = kinds.count("admit")
                # a slot giveback is a policy eviction (preempt) or an
                # admission-dry vacate (v4) — either way the request is
                # requeued and re-admitted, so both close an admit
                evicts = kinds.count("preempt") + kinds.count("vacate")
                finishes = kinds.count("finish")
                cancels = kinds.count("cancel")
                if finishes + cancels > 1:
                    errs.append(f"req {rid}: {finishes + cancels} "
                                f"terminal spans (finish/cancel)")
                # every admit is closed by a preempt/vacate or the
                # terminal finish; an in-flight request may hold one
                # open admit
                if admits < evicts + finishes:
                    errs.append(f"req {rid}: {admits} admits cannot cover "
                                f"{evicts} preempts/vacates + {finishes} "
                                f"finishes")
                if finishes and admits != evicts + finishes:
                    errs.append(f"req {rid}: finished with {admits} "
                                f"admits != {evicts} preempts/vacates + 1")
                # a cancel aborts either a waiting request (its admits all
                # closed by preempts/vacates) or a slot-held one (one
                # open admit)
                if cancels and admits not in (evicts, evicts + 1):
                    errs.append(f"req {rid}: cancelled with {admits} "
                                f"admits, expected {evicts} or "
                                f"{evicts + 1} (= preempts/vacates [+ "
                                f"open slot])")
                # swap accounting (DESIGN.md §13): pages can only stream
                # back in after they were parked
                if kinds.count("swap_in") > kinds.count("swap_out"):
                    errs.append(f"req {rid}: {kinds.count('swap_in')} "
                                f"swap_ins exceed "
                                f"{kinds.count('swap_out')} swap_outs")
            # fixed-bucket p99 must agree with the exact span recompute
            # to within one geometric bucket (~21% ratio; rtol 0.35
            # leaves room for the interpolation inside the bucket)
            hist = (metrics.get("ttft_s") or {})
            if ttft and hist.get("p99") is not None:
                exact = percentile(ttft, 99)
                if exact > 0 and abs(hist["p99"] - exact) > 0.35 * exact:
                    errs.append(f"histogram p99 TTFT {hist['p99']:.6f} "
                                f"vs exact {exact:.6f}: beyond the "
                                f"one-bucket error bound")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize / validate a serving telemetry trace")
    ap.add_argument("path", help="trace file (.jsonl or Chrome .json)")
    ap.add_argument("--check", action="store_true",
                    help="validate structural invariants (exit 1 on "
                         "violation): non-empty, schema-complete ticks, "
                         "token sums == running counters, span pairing, "
                         "histogram-vs-exact p99 agreement")
    args = ap.parse_args(argv)
    meta, ticks, spans, fmt = load(args.path)
    parts = split_replicas(meta, ticks, spans)
    if parts is None:
        summary = summarize(meta, ticks, spans)
        summary["format"] = fmt
        print(json.dumps(summary, indent=1))
        errs = check(meta, ticks, spans, summary) if args.check else []
    else:
        # merged multi-replica trace (ReplicaRouter.dump_trace): per-
        # replica summaries + a fleet rollup, and --check re-runs every
        # tick/span invariant per replica (an idle replica with zero
        # ticks is legitimate, not a violation)
        per = {i: summarize(m, tk, sp) for i, (m, tk, sp) in parts.items()}
        out = {
            "format": fmt,
            "merged": True,
            "fleet": {
                "replicas": len(parts),
                "ticks": sum(s["ticks"] for s in per.values()),
                "packed_tokens": sum(s["packed_tokens"]
                                     for s in per.values()),
                "padded_tokens": sum(s["padded_tokens"]
                                     for s in per.values()),
                "emitted": sum(s["emitted"] for s in per.values()),
                "preemptions": sum(s["preemptions"] for s in per.values()),
                "prefix_hit_tokens": sum(s["prefix_hit_tokens"]
                                         for s in per.values()),
            },
            "replicas": {str(i): s for i, s in per.items()},
        }
        print(json.dumps(out, indent=1))
        errs = []
        if args.check:
            untagged = sum(1 for r in ticks + (spans or [])
                           if "replica" not in r)
            if untagged:
                errs.append(f"merged trace has {untagged} untagged "
                            f"tick/span records")
            for i, (m, tk, sp) in parts.items():
                if not tk:
                    continue
                errs.extend(f"replica {i}: {e}"
                            for e in check(m, tk, sp, per[i]))
    if args.check:
        for e in errs:
            print(f"CHECK FAIL: {e}", file=sys.stderr)
        if errs:
            return 1
        tag = "merged, " if parts is not None else ""
        print(f"# checks passed ({tag}{fmt}: {len(ticks)} ticks"
              + ("" if spans is None else f", {len(spans)} spans") + ")")
    return 0


if __name__ == "__main__":
    sys.exit(main())
