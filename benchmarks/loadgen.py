"""The one general traffic generator: reads a traffic file's parameters.

Lengths and arrival gaps are the stratified quantiles of the file's
distributions, put in an order drawn from the FILE's `schedule_seed`: every
--seed runs the same schedule and changes only the tokens (and the weights).
A cell is thereby a paired comparison on one schedule, and its bounds say
how well that schedule repeats, not how the traffic varies.  Measured in
PR 23: with the order drawn from --seed (the same multisets), the tokens
stamped in a 30 s window moved by 4.8% and the slowest fifth of TTFT by
13-16% from seed to seed (what meets what, and what straddles the window's
edges), against 0.2-0.5% and 1-3% over runs of one order.  A check reads a
cell's spread ACROSS seeds and admits no bound over 10%, so a seed that
reorders the traffic cannot be held to any bound at a window the check
allows (51 s at most).  Another order is another traffic file (a new
`schedule_seed`): a cell of its own, added as data.
"""
from __future__ import annotations

import math
import threading
import time
from statistics import NormalDist

import numpy as np


def stratified(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles of `dist`, as whole numbers clipped to its range:
    a sample with no sampling noise."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        vals = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo = dist.get("min", 1)
    hi = dist.get("max", max(lo, int(vals.max()) + 1))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def stratified_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """n exponential inter-arrival gaps (a Poisson process's) as
    mid-quantiles, scaled to sum to n / rate exactly."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n / rate_per_s) / g.sum()


def open_schedule(traffic: dict, seconds: float, seed: int, vocab: int):
    """Requests of an open loop over lead-in + window: (due offset from the
    window's start, prompt ids, answer length).  The lead-in and the window
    are stratified apart, so the window's mix does not depend on the lead-in."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    ids = np.random.default_rng(seed)
    rate = float(traffic["rate_per_s"])
    out = []
    for start, span in ((-float(traffic["lead_in_s"]),
                         float(traffic["lead_in_s"])), (0.0, float(seconds))):
        n = int(round(rate * span))
        if n <= 0:
            continue
        gaps = rng.permutation(stratified_gaps(rate, n))
        due = start + np.cumsum(gaps) - gaps        # the first at the start
        plen = rng.permutation(stratified(traffic["prompt"], n))
        alen = rng.permutation(stratified(traffic["answer"], n))
        for d, p, a in zip(due, plen, alen):
            out.append((float(d), ids.integers(0, vocab, int(p)), int(a)))
    out.sort(key=lambda r: r[0])
    return out


def closed_pool(traffic: dict, seed: int, vocab: int):
    """Requests of a closed loop: every client's first (its answer cut to
    `first_answer`, so slots open the window at mixed ages) and the shared
    pool the clients draw their next request from, in the file's order."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    ids = np.random.default_rng(seed)
    c, n = int(traffic["clients"]), int(traffic["pool"])
    first = list(zip(rng.permutation(stratified(traffic["prompt"], c)),
                     rng.permutation(stratified(traffic["first_answer"], c))))
    pool = list(zip(rng.permutation(stratified(traffic["prompt"], n)),
                    rng.permutation(stratified(traffic["answer"], n))))
    mk = lambda p, a: (ids.integers(0, vocab, int(p)), int(a))
    return [mk(p, a) for p, a in first], [mk(p, a) for p, a in pool]


class Record:
    """What the benchmark keeps of one request: when it was due and sent,
    and the program's own Request (its token_times are the stamps)."""

    __slots__ = ("due", "sent", "prompt", "answer_len", "req", "error")

    def __init__(self, due, prompt, answer_len):
        self.due, self.prompt, self.answer_len = due, prompt, answer_len
        self.sent, self.req, self.error = None, None, None

    def as_dict(self) -> dict:
        r = self.req
        return {"due": self.due, "sent": self.sent,
                "prompt_len": int(self.prompt.size),
                "answer_len": self.answer_len,
                "token_times": list(r.token_times) if r is not None else [],
                "state": r.state.name if r is not None else "REFUSED",
                "failed": self.error is not None,
                "error": self.error}


class OpenLoop:
    """Sends each request at its due time from a thread of its own, whatever
    the engine is doing (submit() is the engine's any-thread entry)."""

    def __init__(self, submit, schedule, t_window: float):
        self.records = [Record(t_window + d, p, a) for d, p, a in schedule]
        self._submit = submit
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-loadgen")

    def start(self):
        self._thread.start()

    def _run(self):
        for rec in self.records:
            wait = rec.due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            rec.sent = time.perf_counter()
            try:
                rec.req = self._submit(rec.prompt, rec.answer_len)
            except Exception as e:  # noqa: BLE001 - a refusal is a result
                rec.error = f"{type(e).__name__}: {e}"[:200]

    def poll(self):
        """Nothing to do between engine steps: the thread sends."""

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():
            raise RuntimeError("the load generator's thread did not end")


class ClosedLoop:
    """One client a slot; a client sends its next request when its last
    completes.  Driven from the engine's own thread between steps."""

    def __init__(self, submit, first, pool):
        self._submit, self._pool, self._next = submit, pool, 0
        self.records = []
        self._live = []
        self._first = first
        self._stopped = False

    def _send(self, prompt, answer_len):
        now = time.perf_counter()
        rec = Record(now, prompt, answer_len)
        rec.sent = now
        try:
            rec.req = self._submit(prompt, answer_len)
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            rec.error = f"{type(e).__name__}: {e}"[:200]
        self.records.append(rec)
        return rec

    def start(self):
        self._live = [self._send(p, a) for p, a in self._first]

    def poll(self):
        if self._stopped:
            return
        for i, rec in enumerate(self._live):
            if rec.req is None or rec.req.done:
                p, a = self._pool[self._next % len(self._pool)]
                self._next += 1
                self._live[i] = self._send(p, a)

    def stop(self):
        self._stopped = True


def lag_ms_p99(records) -> float | None:
    """How late the generator ran: sent minus due, 99th percentile."""
    lags = sorted(1e3 * (r["sent"] - r["due"]) for r in records
                  if r["sent"] is not None)
    if not lags:
        return None
    return lags[min(len(lags) - 1, math.ceil(0.99 * len(lags)) - 1)]
