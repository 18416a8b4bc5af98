"""What the program-span readers share (no reader itself): the records of
the program's span ring (paddle_tpu/observability/trace.py) with the run's
window on the ring's clock, and self time by the ring's `parent` ids.

modes/serve.py keeps two span names in `ev["spans"]`, so these readers take
the ring themselves: it is kept after the run turns recording off.  A
program without the span a reader names (the parent of the PR that added
it) gives no such record, and the reader then returns None."""
from __future__ import annotations

from fnmatch import fnmatchcase


def window_records(ev):
    """(records, lo, hi, why_not): the ring, oldest first, and the window
    in the ring's nanoseconds; `records` is None, with the reason, where
    the ring lost part of the window (it drops its oldest records)."""
    from paddle_tpu.observability import trace as ptrace
    skew = ev.get("clock_skew_ns", 0)
    lo, hi = ev["t0"] * 1e9 + skew, ev["t1"] * 1e9 + skew
    records = ptrace.trace_records()
    dropped = ptrace.trace_info()["dropped"]
    if dropped and records:
        first = records[0]          # pushed at its end, so the oldest end
        if first["ts"] + (first["dur"] or 0) > lo:
            return None, lo, hi, (f"the span ring dropped {dropped} records "
                                  "and no longer holds the window's start")
    return records, lo, hi, None


def matches(name: str, patterns) -> bool:
    """Whether a span's name matches one of the fnmatch patterns."""
    return any(fnmatchcase(name, p) for p in patterns)


def overlap(rec, lo, hi) -> float:
    """Nanoseconds of a span inside [lo, hi]."""
    return max(0.0, min(rec["ts"] + rec["dur"], hi) - max(rec["ts"], lo))


def less_by_ancestor(records, spans, less, lo, hi) -> dict:
    """{id of a span named in `spans`: nanoseconds, inside [lo, hi], of its
    descendants whose name matches a pattern of `less`}.  A descendant is
    found by walking the ring's `parent` ids upwards; one that lies under
    another match is inside it already and is not taken twice."""
    by_id = {r["id"]: r for r in records}
    match = lambda name: matches(name, less)
    out = {}
    for r in records:
        if r["dur"] is None or not match(r["name"]):
            continue
        p = by_id.get(r["parent"])
        while p is not None and p["name"] not in spans:
            if match(p["name"]):
                p = None
                break
            p = by_id.get(p["parent"])
        if p is not None:
            out[p["id"]] = out.get(p["id"], 0.0) + overlap(r, lo, hi)
    return out
