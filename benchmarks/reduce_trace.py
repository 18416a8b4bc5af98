"""From a jax.profiler trace to the numbers the benchmark prints.

`load_events` turns the profiler's .xplane.pb into plain lists; everything
after it works on those lists, so the tests hold the reduction to a small
recorded trace kept as JSON.  Times are nanoseconds on the host's clock.

What a v5e trace looks like (looked at by hand, PR 23): a plane
`/device:TPU:<n>` a chip, whose line `XLA Ops` holds one event an HLO
instruction executed (named by the instruction's whole text, `%fusion.13 =
bf16[2048,2048]{...} fusion(...)`; a Pallas kernel by its `name=`), with
`while` / `conditional` events spanning their bodies, and `-start` / `-done`
pairs for asynchronous copies and collectives (the `-done` lasts as long as
the core waits).  `XLA Modules` holds one event a program run, with its
`run_id`.  The plane `/host:CPU` has a line a thread: TraceAnnotations,
the Python tracer's `$file:line function` events, and the runtime's own
(`DoEnqueueProgram` carries the `run_id` it launches).  The device's clock
runs about 2 ms ahead of the host's there; `load_events` shifts device
times by the least amount that puts every program's start after its launch.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HEAD = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = \(?([a-z]+[0-9a-z]*)\[([\d,]*)\]")
_COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")
WINDOW_MARK = "bench.trace_window"
NO_HOST_EVENT = "_no_host_event_"
MIN_GAP_NS = 10_000       # shorter gaps are the spaces between ops of a step


def find_xplane(trace_dir: str):
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


KEEP_TRACE_BYTES = 16 << 20


def drop_large(trace_dir: str) -> None:
    """A trace is reduced once and then only takes room (a serving cell's
    is ~100 MB for 3 s, the Python tracer's events most of it): keep the
    .xplane.pb where it is small, drop everything else."""
    import shutil
    path = find_xplane(trace_dir)
    if path and os.path.getsize(path) <= KEEP_TRACE_BYTES:
        for other in glob.glob(os.path.join(os.path.dirname(path), "*")):
            if other != path:
                os.remove(other)
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)


def load_events(trace_dir: str) -> dict | None:
    """{"devices": {id: [[text, start, dur], ...]}, "host": {line: [[name,
    start, dur], ...]}} with device times moved onto the host's clock."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host, launches = {}, [], {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[m.group(1)] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            modules.append((int(rid), e.start_ns))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = []
                for e in line.events:
                    evs.append([e.name, e.start_ns, e.duration_ns])
                    if e.name == "DoEnqueueProgram":
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            launches.setdefault(int(rid), e.start_ns)
                host[line.name] = evs
    shift = max([launches[r] - s for r, s in modules if r in launches],
                default=0.0)
    shift = max(shift, 0.0)
    for evs in devices.values():
        for e in evs:
            e[1] += shift
    return {"devices": devices, "host": host, "device_clock_shift_ns": shift}


# ---- names ------------------------------------------------------------------

def op_name(text: str) -> str:
    """`fusion-bf16_2x4096x14336` from an HLO instruction's text: its name
    without the trailing number, and the type and shape of its (first)
    result, in the characters a metric's name may have."""
    m = _HEAD.match(text)
    if not m:
        return re.sub(r"[^\w.\-]", "_", text.lstrip("%").split(" ")[0])[:64]
    base, dtype, dims = m.groups()
    return f"{base}-{dtype}_{dims.replace(',', 'x') or 'scalar'}"[:64]


def base_name(text: str) -> str:
    m = _HEAD.match(text)
    return m.group(1) if m else text.lstrip("%").split(" ")[0].split(".")[0]


def kernel_events(reduced: dict, kernel: str):
    """(seconds, calls) of a Pallas kernel in a reduced trace.  A kernel
    under a custom_vjp shows with its transform's prefix
    (`transpose_jvp_fused_ce_bwd_dh__`), so the name is looked for as a
    whole word inside the instruction's."""
    pat = re.compile(rf"(?:^|[^a-z0-9]){re.escape(kernel)}(?:$|[^a-z0-9])")
    hits = [b for b in reduced["kernel_s"] if pat.search(b)]
    return (sum(reduced["kernel_s"][b] for b in hits),
            sum(reduced["kernel_calls"][b] for b in hits))


def is_container(text: str) -> bool:
    """An instruction whose event spans the events of its body (XLA names
    it after its opcode)."""
    return base_name(text) in ("while", "conditional", "call")


def is_collective(text: str) -> bool:
    return _COLLECTIVE.search(base_name(text)) is not None


@functools.lru_cache(maxsize=65536)
def _classify(text: str):
    """(printed name, base name, container?, collective?) of an
    instruction; a window repeats the same few hundred texts every step."""
    return (op_name(text), base_name(text), is_container(text),
            is_collective(text))


# ---- intervals --------------------------------------------------------------

def merged(intervals) -> list:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(m) -> float:
    return sum(e - s for s, e in m)


def clipped(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def intersection(a, b) -> float:
    """Length of the overlap of two merged lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps_of(m, t0, t1) -> list:
    """The idle intervals of [t0, t1] left by the merged busy list."""
    out, at = [], t0
    for s, e in m:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


# ---- host attribution -------------------------------------------------------

class HostLine:
    """One thread's events, which nest: finds the event that was open at a
    time, innermost first."""

    def __init__(self, events):
        evs = sorted(events, key=lambda e: (e[1], -e[2]))
        self.names = [e[0] for e in evs]
        self.starts = [e[1] for e in evs]
        self.ends = [e[1] + e[2] for e in evs]
        self.parent, stack = [None] * len(evs), []
        for i in range(len(evs)):
            while stack and self.ends[stack[-1]] <= self.starts[i]:
                stack.pop()
            self.parent[i] = stack[-1] if stack else None
            stack.append(i)

    def covering(self, t: float, at_least_ns: float):
        """Name of the innermost event open at `t` that lasted at least
        `at_least_ns` (an event that explains the gap, not a call that
        happened to be running), or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i is not None and i >= 0:
            if self.ends[i] >= t and \
                    self.ends[i] - self.starts[i] >= at_least_ns and \
                    self.names[i] != WINDOW_MARK:
                return self.names[i]
            i = self.parent[i]
        return None


def main_line(host: dict):
    """The thread that carries the benchmark's own annotations."""
    for evs in host.values():
        if any(e[0] == WINDOW_MARK for e in evs):
            return evs
    return max(host.values(), key=len, default=[])


def window_of(events: dict):
    """[start, end] of the traced span: the benchmark's mark, or failing
    that the extent of the device's events."""
    for evs in events["host"].values():
        for name, s, d in evs:
            if name == WINDOW_MARK:
                return s, s + d
    flat = [e for evs in events["devices"].values() for e in evs]
    if not flat:
        return None
    return min(e[1] for e in flat), max(e[1] + e[2] for e in flat)


# ---- the reduction ----------------------------------------------------------

def reduce(events: dict) -> dict | None:
    if not events or not events["devices"]:
        return None
    win = window_of(events)
    if win is None or win[1] <= win[0]:
        return None
    t0, t1 = win
    per_dev, by_name, kernel_ns, kernel_calls = {}, {}, {}, {}
    for dev, evs in sorted(events["devices"].items()):
        all_, coll, comp = [], [], []
        for text, s, d in evs:
            if d <= 0:
                continue
            name, base, container, collective = _classify(text)
            all_.append((s, s + d))
            if collective:
                coll.append((s, s + d))
            elif not container:
                comp.append((s, s + d))
            if container or s + d <= t0 or s >= t1:
                continue
            d_in = min(s + d, t1) - max(s, t0)
            by_name[name] = by_name.get(name, 0.0) + d_in
            kernel_ns[base] = kernel_ns.get(base, 0.0) + d_in
            kernel_calls[base] = kernel_calls.get(base, 0) + 1
        busy, coll, comp = (merged(clipped(x, t0, t1))
                            for x in (all_, coll, comp))
        per_dev[dev] = {
            "busy_ns": measure(busy), "gaps": gaps_of(busy, t0, t1),
            "collective_ns": measure(coll),
            "collective_exposed_ns": measure(coll) - intersection(coll, comp)}
    n_dev = len(per_dev)
    window_ns = t1 - t0
    idlest = min(per_dev, key=lambda d: per_dev[d]["busy_ns"])
    line = HostLine(main_line(events["host"]))
    gap_by = {}
    for s, e in per_dev[idlest]["gaps"]:
        if e - s < MIN_GAP_NS:
            continue
        who = line.covering((s + e) / 2.0, 0.5 * (e - s)) or NO_HOST_EVENT
        gap_by[who] = gap_by.get(who, 0.0) + (e - s)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(p["busy_ns"] for p in per_dev.values()) / n_dev / 1e9,
        "busy_s_by_device": {d: p["busy_ns"] / 1e9 for d, p in per_dev.items()},
        "idle_share": 1.0 - sum(p["busy_ns"] for p in per_dev.values())
        / n_dev / window_ns,
        "collective_exposed_share_worst": max(
            p["collective_exposed_ns"] for p in per_dev.values()) / window_ns,
        "collective_share_worst": max(
            p["collective_ns"] for p in per_dev.values()) / window_ns,
        "device_ops": top({k: v / n_dev for k, v in by_name.items()}),
        "idle_gaps": top({re.sub(r"[^\w.\-]", "_", k)[:64]: v
                          for k, v in gap_by.items()}),
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": {k: v / n_dev for k, v in kernel_calls.items()},
        "devices": n_dev,
        "device_clock_shift_ns": events.get("device_clock_shift_ns", 0.0),
    }
