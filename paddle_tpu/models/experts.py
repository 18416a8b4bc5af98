"""Routed experts: a sparse feed-forward that drops nothing.

    s = sigmoid(x W_r)                    over ALL `num_experts`, float32
    sel = top_k(s + b)                    b: a selection-only bias
    w_i = s_i / sum_{j in sel} s_j        (`norm_topk_prob`), times a scale
    y = sum_{i in sel} w_i E_i(x)         E_i a SwiGLU of width `inter`

The layer is TOLD which experts it holds, `held = (first, count)`: it routes
over all of them, normalises over all `top_k` selected, and computes the part
of y that its own experts give.  What the absent experts would add is left
out (the other shares hold them: summed over a partition of the experts the
shares are the whole layer), and no code stands in for the absent chips.

Nothing is dropped at any imbalance: there is no capacity factor.  Shapes are
static for the capture tier: the `T x top_k` assignments are laid out by held
expert in whole row tiles, the ones routed elsewhere nowhere, and
`ops/pallas/grouped_expert_matmul.py` skips the row tiles past the live
count, so the work follows the live count.  Experts are stacked, one array a
projection (`gate_up_proj [count, hidden, 2 * inter]`, `down_proj
[count, inter, hidden]`): two parameters a layer beside the router's two,
whatever the count.  More than `chunk_tokens` tokens run as a `lax.map` over
chunks, so the worst-case layout is a chunk's and not a whole prompt's.

`distributed/fleet/moe.py` is the Paddle-compatible layer (capacity-factor
gates that drop, `global_scatter/gather`); this one is for serving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn.initializer import Normal, XavierNormal
from ..nn.layer.layers import Layer
from ..ops.dispatch import apply
from ..ops.pallas.grouped_expert_matmul import (
    TILE_ROWS, group_row_starts, grouped_expert_matmul, padded_rows)

# what `routed_experts` counts, in the order of its int32 vector; the last
# name stands for one entry a held expert
COUNTER_NAMES = ("moe_assignments", "moe_assignments_local",
                 "moe_experts_hit", "moe_expert_tokens")


def route(x, w_router, bias, *, top_k, norm_topk=True, scale=1.0,
          router_dtype="float32"):
    """The router alone: x [T, hidden] -> (sel [T, top_k] int32, w [T, top_k]
    float32).  Logits, sigmoid scores and the top-k are `router_dtype`
    (float32: a bfloat16 score has 8 bits and top-8 of 256 flips on its
    rounding); the same on every share, whatever it holds."""
    rd = jnp.dtype(router_dtype)
    logits = jax.lax.dot_general(
        x.astype(rd), w_router.astype(rd), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=rd).astype(jnp.float32)
    s = jax.nn.sigmoid(logits.astype(rd)).astype(jnp.float32)
    biased = (s.astype(rd) + bias.astype(rd)).astype(jnp.float32)
    _, sel = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * scale


def _experts_chunk(x, sel, w, gate_up, down, first, tile_rows):
    """One chunk's share: x [T, hidden], sel / w [T, top_k].  Returns
    (y [T, hidden] float32, tokens a held expert [count] int32, held
    experts with at least one token: what the two matmuls read)."""
    t, k = sel.shape
    count, _, two_inter = gate_up.shape
    a = t * k
    rows = padded_rows(a, count, tile_rows)
    le = sel.reshape(a) - first
    local = (le >= 0) & (le < count)
    le = jnp.where(local, le, count)
    onehot = (le[:, None] == jnp.arange(count, dtype=jnp.int32)[None])
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)          # [count]
    starts = group_row_starts(counts, tile_rows)               # [count]
    # an assignment's row: its group's first row + how many of the group
    # came before it (no scatter: a cumulative sum and two gathers)
    before = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1  # [a, count]
    safe = jnp.minimum(le, count - 1)
    rank = jnp.take_along_axis(before, safe[:, None], axis=1)[:, 0]
    dest = jnp.where(local, starts[safe] + rank, 0)            # [a]
    # a row's token: sort the assignments by (group, index); row r of group
    # g is the (r - starts[g])-th of them
    order = jnp.sort(le * a + jnp.arange(a, dtype=jnp.int32)) % a
    firsts = jnp.cumsum(counts) - counts           # group starts, end to end
    r = jnp.arange(rows, dtype=jnp.int32)
    ends = starts + (-(-counts // tile_rows)) * tile_rows
    g = jnp.minimum(jnp.searchsorted(ends, r, side="right"), count - 1)
    within = r - starts[g]
    src = jnp.where(within < counts[g],
                    order[jnp.clip(firsts[g] + within, 0, a - 1)] // k, 0)
    xs = x[src]                                                # [rows, hidden]
    gu = grouped_expert_matmul(xs, gate_up, counts, tile_rows)
    inter = two_inter // 2
    act = (jax.nn.silu(gu[:, :inter].astype(jnp.float32))
           * gu[:, inter:].astype(jnp.float32)).astype(x.dtype)
    ys = grouped_expert_matmul(act, down, counts, tile_rows)   # [rows, hidden]
    # rows of dead tiles are unspecified: select, do not multiply by zero
    mine = jnp.where(local[:, None], ys[dest].astype(jnp.float32), 0.0)
    y = jnp.sum(mine.reshape(t, k, -1) * w[:, :, None], axis=1)
    return y, counts, jnp.sum(counts > 0, dtype=jnp.int32)


def routed_experts(x, w_router, bias, gate_up, down, *, top_k, first,
                   norm_topk=True, scale=1.0, router_dtype="float32",
                   tile_rows=TILE_ROWS, chunk_tokens=1024):
    """x [..., hidden] -> (y [..., hidden] in x's type: this share of the
    layer's output; counters [3 + count] int32, `COUNTER_NAMES`: all
    assignments computed here (padding rows of a bucket and idle slots are
    tokens like any other: the device routes them), those on held experts,
    held experts with at least one token, tokens a held expert)."""
    lead, hidden = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, hidden)
    t = x2.shape[0]
    sel, w = route(x2, w_router, bias, top_k=top_k, norm_topk=norm_topk,
                   scale=scale, router_dtype=router_dtype)
    if t <= chunk_tokens:
        y, counts, hit = _experts_chunk(x2, sel, w, gate_up, down, first,
                                        tile_rows)
    else:
        pad = (-t) % chunk_tokens
        chunks = lambda v: jnp.pad(v, ((0, pad), (0, 0))).reshape(
            (-1, chunk_tokens) + v.shape[1:])
        # a padding token selects no expert: nothing of it is local
        y, counts, hit = jax.lax.map(
            lambda c: _experts_chunk(*c, gate_up, down, first, tile_rows),
            (chunks(x2), jnp.pad(sel, ((0, pad), (0, 0)), constant_values=-1)
             .reshape(-1, chunk_tokens, top_k), chunks(w)))
        y, counts, hit = (y.reshape(-1, hidden)[:t], jnp.sum(counts, axis=0),
                          jnp.sum(hit))
    stats = jnp.concatenate([
        jnp.asarray([t * top_k], jnp.int32), jnp.sum(counts)[None],
        hit[None], counts]).astype(jnp.int32)
    return y.astype(x.dtype).reshape(lead + (hidden,)), stats


class RoutedExperts(Layer):
    """The layer over `routed_experts`; `forward` returns (y, counters).
    `num_experts` is the router's width, `held = (first, count)` the experts
    whose weights this layer has (default: all).  The router's two
    parameters are created float32; a cast of the whole model rounds them
    with everything else, and `router_dtype` is what they are COMPUTED in."""

    def __init__(self, hidden_size: int, inter_size: int, num_experts: int,
                 top_k: int, held=None, norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0,
                 router_dtype: str = "float32", tile_rows: int = TILE_ROWS,
                 chunk_tokens: int = 1024):
        super().__init__()
        first, count = held if held is not None else (0, num_experts)
        if not (0 <= first and first + count <= num_experts and count > 0):
            raise ValueError(f"held {held!r} is not a run of the "
                             f"{num_experts} experts")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.held = (int(first), int(count))
        self.options = dict(
            top_k=top_k, first=int(first), norm_topk=bool(norm_topk_prob),
            scale=float(routed_scaling_factor or 1.0),
            router_dtype=router_dtype, tile_rows=tile_rows,
            chunk_tokens=chunk_tokens)
        self.router_weight = self.create_parameter(
            [hidden_size, num_experts], dtype="float32",
            default_initializer=XavierNormal())
        stack = XavierNormal(fan_in=hidden_size, fan_out=inter_size)
        # small and not zero: a selection that ignored it would differ
        self.router_bias = self.create_parameter(
            [num_experts], dtype="float32", is_bias=True,
            default_initializer=Normal(0.0, 0.01))
        self.gate_up_proj = self.create_parameter(
            [count, hidden_size, 2 * inter_size], default_initializer=stack)
        self.down_proj = self.create_parameter(
            [count, inter_size, hidden_size], default_initializer=stack)

    def forward(self, x):
        return apply(routed_experts, x, self.router_weight, self.router_bias,
                     self.gate_up_proj, self.down_proj,
                     op_name="routed_experts", **self.options)
