#!/usr/bin/env python
"""Merge per-process flight dumps (+ optional Chrome traces) onto ONE
wall-clock timeline.

Every zoo process keeps its own flight ring and trace clock — each is
self-consistent but says nothing about the others.  ISSUE 17 gave both
a ``(monotonic, epoch)`` anchor: flight events carry ``mono``
(CLOCK_MONOTONIC — shared by every process of one boot) next to ``ts``
(epoch), and traces carry a ``clock_anchor`` in their metadata mapping
trace-µs 0 to both clocks.  This tool consumes the anchors:

1. every input's per-process ``epoch - monotonic`` offset is estimated;
2. the MEDIAN offset becomes the reference clock — so one process with
   a skewed wall clock is corrected toward the cohort instead of
   dragging the merged timeline with it (same-host processes share
   CLOCK_MONOTONIC exactly, making the correction exact there);
3. all events are emitted on the reference timeline, as
   - a **narrative**: one chronological line per flight event, tagged
     with its source process — the artifact that explains a chaos run
     end-to-end (every generation change, takeover and respawn appears
     next to its cause), and
   - a **merged Chrome trace**: flight events as instant events plus
     every input trace's spans shifted onto the shared clock — load the
     single file in Perfetto and see the whole pod.

Usage::

    python tools/flight_merge.py FLIGHT_DIR_OR_FILES...
        [--trace trace.json ...] [--out merged_trace.json]
        [--narrative narrative.txt] [--skew-tolerance-s 0.25]

Library surface (used by tests): :func:`load_inputs`,
:func:`merge_flight_docs`, :func:`write_outputs`.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def load_inputs(paths) -> list[dict]:
    """Flight docs from files, directories (``flight-*.json``), or
    globs; each doc is tagged with its source path."""
    files = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(
                os.path.join(p, "flight-*.json"))))
        elif any(ch in p for ch in "*?["):
            files.extend(sorted(glob.glob(p)))
        else:
            files.append(p)
    docs = []
    for f in files:
        try:
            with open(f) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"flight_merge: skipping {f}: {e}", file=sys.stderr)
            continue
        doc["_path"] = f
        docs.append(doc)
    return docs


def _doc_offset(doc: dict) -> float | None:
    """This process's ``epoch - monotonic`` offset, from the doc anchor
    or (better — closer to the events) the median per-event pair."""
    pairs = [(e["ts"], e["mono"]) for e in doc.get("events", ())
             if "mono" in e and "ts" in e]
    if pairs:
        offs = sorted(ts - mono for ts, mono in pairs)
        return offs[len(offs) // 2]
    anchor = doc.get("clock_anchor") or {}
    if "epoch" in anchor and "monotonic" in anchor:
        return float(anchor["epoch"]) - float(anchor["monotonic"])
    return None


def merge_flight_docs(docs: list[dict],
                      skew_tolerance_s: float = 0.25) -> dict:
    """One timeline from many flight docs.

    Returns ``{"timeline": [...], "skew": {...}, "sources": n}`` —
    timeline events carry ``t`` (reference epoch seconds), ``src``
    (``pid@reason`` of the dump), and the original fields.  ``skew``
    reports each source's wall-clock offset from the cohort median and
    whether it exceeded ``skew_tolerance_s`` (corrected either way when
    the event has a ``mono`` field; epoch-only events are trusted
    as-is)."""
    offsets = {}
    for i, doc in enumerate(docs):
        off = _doc_offset(doc)
        if off is not None:
            offsets[i] = off
    ref = None
    if offsets:
        vals = sorted(offsets.values())
        ref = vals[len(vals) // 2]
    timeline = []
    skew = {}
    for i, doc in enumerate(docs):
        src = "%s@%s" % (doc.get("pid", "?"), doc.get("reason", "?"))
        off = offsets.get(i)
        if off is not None and ref is not None:
            skew[src] = {
                "offset_s": round(off - ref, 6),
                "beyond_tolerance":
                    abs(off - ref) > skew_tolerance_s,
                "path": doc.get("_path"),
            }
        for ev in doc.get("events", ()):
            if "mono" in ev and ref is not None:
                # the shared monotonic clock + reference offset beats
                # trusting this process's wall clock
                t = float(ev["mono"]) + ref
            else:
                t = float(ev.get("ts", 0.0))
            timeline.append({"t": t, "src": src, **{
                k: v for k, v in ev.items() if k != "mono"}})
    timeline.sort(key=lambda e: e["t"])
    return {"timeline": timeline, "skew": skew, "sources": len(docs)}


def narrative_lines(merged: dict) -> list[str]:
    """Human-readable chronology: relative seconds, source, kind, and
    the event's own fields."""
    timeline = merged["timeline"]
    if not timeline:
        return []
    t0 = timeline[0]["t"]
    lines = []
    for ev in timeline:
        fields = " ".join(
            f"{k}={ev[k]}" for k in sorted(ev)
            if k not in ("t", "ts", "src", "kind"))
        lines.append("%10.3fs  %-16s %-14s %s" % (
            ev["t"] - t0, ev["src"], ev.get("kind", "?"), fields))
    return lines


def _load_traces(paths) -> list[dict]:
    out = []
    for p in paths:
        try:
            with open(p) as fh:
                out.append(json.load(fh))
        except (OSError, json.JSONDecodeError) as e:
            print(f"flight_merge: skipping trace {p}: {e}",
                  file=sys.stderr)
    return out


def merged_chrome_trace(merged: dict, traces=()) -> dict:
    """Flight events as instant events + input traces' spans, all on
    the reference clock (µs since the merged timeline's first event)."""
    timeline = merged["timeline"]
    t0 = timeline[0]["t"] if timeline else 0.0
    events = []
    for ev in timeline:
        args = {k: v for k, v in ev.items()
                if k not in ("t", "src", "kind")}
        pid = ev["src"].split("@", 1)[0]
        events.append({
            "name": ev.get("kind", "?"), "ph": "i", "s": "p",
            "ts": max(0.0, (ev["t"] - t0) * 1e6),
            "pid": int(pid) if str(pid).isdigit() else 0,
            "tid": 0, "cat": "flight", "args": args,
        })
    for doc in traces:
        anchor = (doc.get("metadata") or {}).get("clock_anchor") or {}
        epoch0 = anchor.get("epoch")
        if epoch0 is None:
            continue  # unanchored trace: cannot place on shared clock
        shift_us = (float(epoch0) - t0) * 1e6
        for ev in doc.get("traceEvents", ()):
            ev = dict(ev)
            ev["ts"] = float(ev.get("ts", 0.0)) + shift_us
            events.append(ev)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {
            "producer": "tools/flight_merge.py",
            "sources": merged["sources"],
            "skew": merged["skew"],
            "t0_epoch": t0,
        },
    }


def write_outputs(merged: dict, traces=(), out: str | None = None,
                  narrative: str | None = None) -> dict:
    paths = {}
    if out:
        with open(out, "w") as f:
            json.dump(merged_chrome_trace(merged, traces), f)
        paths["trace"] = out
    if narrative:
        with open(narrative, "w") as f:
            f.write("\n".join(narrative_lines(merged)) + "\n")
        paths["narrative"] = narrative
    return paths


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="flight_merge",
        description="merge per-process flight dumps (and traces) onto "
                    "one wall-clock timeline")
    p.add_argument("inputs", nargs="+",
                   help="flight dump files, dirs, or globs")
    p.add_argument("--trace", action="append", default=[],
                   help="Chrome-trace JSON to fold in (repeatable)")
    p.add_argument("--out", default=None,
                   help="write merged Chrome trace JSON here")
    p.add_argument("--narrative", default=None,
                   help="write the event narrative here (default: "
                        "stdout)")
    p.add_argument("--skew-tolerance-s", type=float, default=0.25,
                   help="flag sources whose wall clock deviates more "
                        "than this from the cohort median")
    a = p.parse_args(argv)

    docs = load_inputs(a.inputs)
    if not docs:
        print("flight_merge: no flight dumps found", file=sys.stderr)
        return 2
    merged = merge_flight_docs(docs,
                               skew_tolerance_s=a.skew_tolerance_s)
    traces = _load_traces(a.trace)
    write_outputs(merged, traces, out=a.out, narrative=a.narrative)
    if not a.narrative:
        for line in narrative_lines(merged):
            print(line)
    bad = [s for s, v in merged["skew"].items()
           if v["beyond_tolerance"]]
    print(f"# {merged['sources']} sources, "
          f"{len(merged['timeline'])} events"
          + (f", skew beyond tolerance: {', '.join(bad)}" if bad
             else ""), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
