"""The arithmetic behind every number the benchmark prints.

Pure functions of stamps, records and sizes: no JAX, no program code, so the
tests hold them to synthetic inputs and a later PR cannot move a metric by
moving a counter.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, q in 0..100; None on no samples."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# ---- training ---------------------------------------------------------------

def step_intervals(stamps: Sequence[float], t0: float, t1: float) -> list:
    """Gaps between the completions of consecutive steps, both inside
    [t0, t1].  Whole steps only: a step cut by an edge of the window gives
    no gap, so the edges cannot add or lose a step's worth."""
    inside = [s for s in stamps if t0 <= s <= t1]
    return [b - a for a, b in zip(inside, inside[1:])]


def whole_step_throughput(stamps, t0, t1, tokens_per_step) -> Optional[float]:
    """Tokens of all the whole steps in the window over all the time they
    took: (completions inside - 1) x tokens a step / (last completion -
    first completion).  A stalled step is in it and lowers it; the part of
    a step an edge of the window cuts off is neither in the tokens nor in
    the time, so where the window opens in a step does not move it."""
    gaps = step_intervals(stamps, t0, t1)
    if not gaps:
        return None
    return len(gaps) * tokens_per_step / sum(gaps)


def matmul_params(cfg: dict, depth: int) -> int:
    """Parameters a token is multiplied by: the decoder layers' projections
    and the output head; not the embedding table (a lookup) and not norms."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = h * cfg["num_attention_heads"] * d
    kv = 2 * h * cfg["num_key_value_heads"] * d
    o = cfg["num_attention_heads"] * d * h
    layer = q + kv + o + 3 * h * m
    return depth * layer + h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, depth: int, seq_len: int) -> float:
    """Operations the forward and backward passes need for one token:
    6 x matmul parameters, plus causal attention, 2 x S x hidden forward
    (QK^T and PV at half the square) and twice that backward, a layer.
    Recomputed operations are not counted."""
    attn = 6.0 * depth * seq_len * cfg["num_attention_heads"] * (
        cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    return 6.0 * matmul_params(cfg, depth) + attn


def mfu_percent(tokens_per_s, flops_per_token, chips, peak_flops) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops)


# ---- serving ----------------------------------------------------------------

def ttft_values(requests: Iterable[dict], t0: float, t1: float) -> list:
    """Seconds from the time a request was DUE (open loop: the wait a stall
    imposes counts) to its first token, for requests due in [t0, t1).  A
    request that failed or got no first token counts as the window."""
    out = []
    for r in requests:
        if not (t0 <= r["due"] < t1):
            continue
        if r.get("failed") or not r["token_times"]:
            out.append(t1 - t0)
        else:
            out.append(r["token_times"][0] - r["due"])
    return out


def slowest_fifth_mean(values: Sequence[float]) -> Optional[float]:
    """Mean of the slowest 20% (at least one): a tail with a fifth of the
    sample behind it, where a p95 of a hundred requests rests on five."""
    if not values:
        return None
    v = sorted(values)
    k = max(1, math.ceil(len(v) / 5))
    return sum(v[-k:]) / k


def itl_gaps(requests: Iterable[dict], t0: float, t1: float) -> list:
    """Gaps between consecutive tokens of one request whose later token
    fell in [t0, t1], over all requests."""
    out = []
    for r in requests:
        tt = r["token_times"]
        out.extend(b - a for a, b in zip(tt, tt[1:]) if t0 <= b <= t1)
    return out


def tokens_in_window(requests: Iterable[dict], t0: float, t1: float) -> int:
    return sum(1 for r in requests for t in r["token_times"] if t0 <= t <= t1)


def out_tokens_per_s(requests, t0, t1) -> float:
    """Output tokens stamped inside the window over the whole window."""
    return tokens_in_window(requests, t0, t1) / (t1 - t0)
