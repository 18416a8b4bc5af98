"""MiMo-V2-Flash: sliding-window and full attention mixed in one decoder,
routed experts in every layer but the first (Xiaomi's published
`config.json` keys are `MiMoConfig`'s fields).

Every layer is `h = x + attn(norm(x)); y = h + ffn(norm(h))`, RMSNorm, an
untied head.  `hybrid_layer_pattern[i]` says which attention layer i has:

- 0, full: `num_key_value_heads` KV heads, `rope_theta`, a causal mask;
- 1, window: `swa_num_key_value_heads` KV heads, `swa_rope_theta`, key j
  visible to query i iff `0 <= i - j < sliding_window`, and one learned
  logit a query head (`sink`) that joins the softmax's denominator and
  carries no value.

Both: q and k heads of `head_dim` lanes of which the leading
`int(head_dim * partial_rotary_factor)` are rotated (half-split pairs), v
heads of `v_head_dim` lanes scaled by `attention_value_scale`, scores over
`sqrt(head_dim)`.  `moe_layer_freq[i]` says whether layer i's feed-forward is
the dense SwiGLU (`LlamaMLP`) or routed experts (`models/experts.py`, which
knows nothing of this model): `held_experts = (first, count)` is the share
of the `n_routed_experts` this chip holds.

A request's state is of two kinds, and `init_kv_caches` hands the serving
engine both as one pytree, a (K, V) pair a layer, every leaf with the slot
axis first, as the (position, KV head) rows the decode kernel reads in
place, K's lanes padded once at allocation (`cache_lanes`):

- full layer, kind "kv": `[B, S_max * H_kv, lanes]`, a row block a position;
- window layer, kind "window": `[B, window * H_kv, lanes]`, a RING: position
  p lives in row block `p % window`.  K is cached after rotation, so the
  order of a ring's rows does not matter to a softmax.  A ring is a fixed
  cost a slot that cannot be shared by prefix, rewound or cut into chunks.

A step takes `lengths` [B], the REAL tokens of each row's window: a prefill
under right padding keeps in the ring the last `min(length, window)` real
positions.  A window of more than one position is a prefill FROM AN EMPTY
CACHE (the engine's, at offset 0): it attends over its own keys through
`ops/pallas/flash_attention.py windowed_flash_attention`; one position goes
through `ragged_decode_attention` over the cache.  No window (verify) step:
`cache_kinds()` says why.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..nn.initializer import Normal
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops.dispatch import apply
from ..ops.pallas.decode_attention import cache_lanes
from .experts import COUNTER_NAMES, RoutedExperts
from .jamba import tied_lm_head
from .llama import LlamaMLP, _rope


@dataclass
class MiMoConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    hybrid_layer_pattern: List[int] = field(default_factory=lambda: [
        0 if i % 6 == 5 or i == 0 else 1 for i in range(48)])
    moe_layer_freq: List[int] = field(default_factory=lambda: [
        int(i > 0) for i in range(48)])
    sliding_window: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scaling_factor: Optional[float] = None
    n_group: int = 1
    topk_group: int = 1
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    # not in the published file: this chip's share of the routed experts
    # (first, count), default all; and what the router is computed in
    held_experts: Optional[Tuple[int, int]] = None
    router_dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.hybrid_layer_pattern) < n or len(self.moe_layer_freq) < n:
            raise ValueError(
                f"{n} layers need {n} entries of hybrid_layer_pattern and "
                "moe_layer_freq")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                "group-limited routing (n_group, topk_group > 1) is not built")
        if self.scoring_func != "sigmoid":
            raise NotImplementedError(
                f"scoring_func {self.scoring_func!r}: the expert layer "
                "scores with a sigmoid, which is all a configuration asks")
        if (self.swa_num_attention_heads, self.swa_head_dim,
                self.swa_v_head_dim) != (self.num_attention_heads,
                                         self.head_dim, self.v_head_dim):
            raise NotImplementedError(
                "window and full layers share their query heads and lanes")
        if self.tie_word_embeddings:
            raise NotImplementedError("the head is untied")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_window(self, layer: int) -> bool:
        return bool(self.hybrid_layer_pattern[layer])

    def is_moe(self, layer: int) -> bool:
        return bool(self.moe_layer_freq[layer])

    @staticmethod
    def tiny(vocab=128, hidden=64, inter=128, moe_inter=32, heads=4,
             kv_heads=1, swa_kv_heads=2, head_dim=24, v_head_dim=16,
             window=8, experts=16, held=None, top_k=2, seq=64,
             pattern=(0, 1, 1, 0, 1), moe=(0, 1, 1, 1, 1)):
        return MiMoConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            moe_intermediate_size=moe_inter, num_hidden_layers=len(pattern),
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            head_dim=head_dim, v_head_dim=v_head_dim,
            swa_num_attention_heads=heads,
            swa_num_key_value_heads=swa_kv_heads, swa_head_dim=head_dim,
            swa_v_head_dim=v_head_dim, hybrid_layer_pattern=list(pattern),
            moe_layer_freq=list(moe), sliding_window=window,
            n_routed_experts=experts, num_experts_per_tok=top_k,
            held_experts=held, max_position_embeddings=seq)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def ring_rows(new, lens, window):
    """The ring a prefill from an empty cache leaves: new [B, S, ...], the
    window's keys (or values) by position; lens [B] its real positions.  Row
    r of the result [B, window, ...] holds the one position p in
    [len - window, len) with p % window == r, zeros where that is negative
    (right padding does not enter it)."""
    s = new.shape[1]
    lo = lens[:, None] - window                              # [B, 1]
    p = lo + jnp.mod(jnp.arange(window)[None] - lo, window)  # [B, window]
    rows = jax.vmap(lambda n, i: n[i])(new, jnp.clip(p, 0, s - 1))
    keep = (p >= 0).reshape(p.shape + (1,) * (new.ndim - 2))
    return jnp.where(keep, rows, jnp.zeros((), new.dtype))


def partial_rope(q, k, v, off, *, theta, rotary_dim, value_scale=1.0):
    """What a layer does to its projections before it attends: q and k
    [B, S, heads, D] rotate in their leading `rotary_dim` lanes (half-split
    pairs, `models/llama.py _rope`) at positions `off` [B] on, v is scaled.
    K is cached as it leaves here."""
    q, k = _rope(q, k, theta, off, rotary_dim=rotary_dim, half_split=True)
    return q, k, (v * value_scale).astype(v.dtype)


def mimo_attention(q, kn, vn, kc, vc, off, lens, sink=None, *, window=None):
    """One attention layer over its cache.  q [B, S, H, D]; kn [B, S, H_kv,
    D] (rotated) and vn [B, S, H_kv, D_v] (scaled), the window's new keys
    and values; kc, vc the cache as rows, [B, positions * H_kv, lanes]
    (`positions` is `window` for a ring); off [B] where each row's window
    starts, lens [B] its real positions; sink [H] or None.  Writes the
    window into the cache, then attends.  Returns the output [B, S, H, D_v]
    and both caches."""
    b, s, _, d = q.shape
    hkv, dv = kn.shape[2], vn.shape[3]
    scale = 1.0 / math.sqrt(d)
    lanes = lambda n, c: jnp.pad(
        n, ((0, 0),) * 3 + ((0, c.shape[2] - n.shape[3]),)).astype(c.dtype)
    kp, vp = lanes(kn, kc), lanes(vn, vc)
    if s == 1:
        from ..ops.pallas.decode_attention import ragged_decode_attention
        from ..ops.pallas.kv_cache_append import kv_cache_append, whole_tiles
        pos = off if window is None else jnp.mod(off, window)
        if whole_tiles(hkv, kc.shape[2], kc.dtype) \
                and whole_tiles(hkv, vc.shape[2], vc.dtype):
            kc, vc = kv_cache_append(kc, vc, kp[:, 0], vp[:, 0], pos)
        else:
            # half a tile a position: the row copy is refused and the
            # vmapped write stays (ops/pallas/kv_cache_append.py)
            z = jnp.zeros_like(pos)
            put = jax.vmap(lambda c, n, o: jax.lax.dynamic_update_slice(
                c, n, (o * hkv, z[0])))
            kc, vc = put(kc, kp[:, 0], pos), put(vc, vp[:, 0], pos)
        live = off + 1 if window is None else jnp.minimum(off + 1, window)
        out = ragged_decode_attention(q, kc, vc, live.astype(jnp.int32),
                                      scale=scale, sink=sink,
                                      num_kv_heads=hkv)
        return out[..., :dv], kc, vc
    from ..ops.pallas.flash_attention import windowed_flash_attention
    out = windowed_flash_attention(q, kn, vn, sink, window, scale)
    rows = lambda n: n.reshape(b, -1, n.shape[-1])
    if window is None:
        put = jax.vmap(lambda c, n, o: jax.lax.dynamic_update_slice(
            c, n, (o * hkv, jnp.zeros_like(o))))
        kc, vc = put(kc, rows(kp), off), put(vc, rows(vp), off)
    else:
        kc = rows(ring_rows(kp, lens, window))
        vc = rows(ring_rows(vp, lens, window))
    return out, kc, vc


class MiMoAttention(Layer):
    def __init__(self, config: MiMoConfig, window: bool):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.swa_num_key_value_heads if window \
            else config.num_key_value_heads
        self.head_dim, self.v_head_dim = config.head_dim, config.v_head_dim
        self.rotary_dim = config.rotary_dim
        self.theta = config.swa_rope_theta if window else config.rope_theta
        self.window = config.sliding_window if window else None
        self.value_scale = config.attention_value_scale
        nh, hkv = self.num_heads, self.num_kv_heads
        self.q_proj = ColumnParallelLinear(h, nh * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, hkv * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(h, hkv * self.v_head_dim,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(nh * self.v_head_dim, h,
                                        has_bias=False, input_is_parallel=True)
        self.use_sink = config.add_swa_attention_sink_bias if window \
            else config.add_full_attention_sink_bias
        if self.use_sink:
            # small and not zero: leaving it out would differ
            self.sink = self.create_parameter(
                [nh], dtype="float32", is_bias=True,
                default_initializer=Normal(0.0, 0.5))

    def forward(self, x, kv_cache, position_offset, lengths):
        b, s = x.shape[0], x.shape[1]
        nh, hkv = self.num_heads, self.num_kv_heads
        q = self.q_proj(x).reshape([b, s, nh, self.head_dim])
        k = self.k_proj(x).reshape([b, s, hkv, self.head_dim])
        v = self.v_proj(x).reshape([b, s, hkv, self.v_head_dim])
        off = jnp.broadcast_to(jnp.asarray(_val(position_offset), jnp.int32),
                               (b,))
        q, k, v = apply(partial_rope, q, k, v, off, op_name="partial_rope",
                        theta=self.theta, rotary_dim=self.rotary_dim,
                        value_scale=self.value_scale)
        sink = (self.sink,) if self.use_sink else ()
        attn, kc, vc = apply(mimo_attention, q, k, v, kv_cache[0],
                             kv_cache[1], off, lengths, *sink,
                             op_name="mimo_attention", window=self.window)
        return self.o_proj(attn.reshape([b, s, nh * self.v_head_dim])), \
            (kc, vc)


class MiMoDecoderLayer(Layer):
    def __init__(self, config: MiMoConfig, window: bool, moe: bool):
        super().__init__()
        self.is_window, self.is_moe = window, moe
        eps = config.layernorm_epsilon
        self.input_layernorm = RMSNorm(config.hidden_size, eps)
        self.self_attn = MiMoAttention(config, window)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, eps)
        self.mlp = RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            held=config.held_experts, norm_topk_prob=config.norm_topk_prob,
            routed_scaling_factor=config.routed_scaling_factor or 1.0,
            router_dtype=config.router_dtype) if moe else LlamaMLP(config)

    def forward(self, x, cache, position_offset, lengths):
        """Returns the layer's output, its cache and the expert layer's
        counters (None for the dense feed-forward)."""
        mixed, cache = self.self_attn(self.input_layernorm(x), cache,
                                      position_offset, lengths)
        h = x + mixed
        y = self.mlp(self.post_attention_layernorm(h))
        y, stats = y if self.is_moe else (y, None)
        return h + y, cache, stats


class MiMoModel(Layer):
    def __init__(self, config: MiMoConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([
            MiMoDecoderLayer(config, config.is_window(i), config.is_moe(i))
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.layernorm_epsilon)

    def forward(self, input_ids, caches, position_offset, lengths):
        x = self.embed_tokens(input_ids)
        new_caches, stats = [], None
        for layer, cache in zip(self.layers, caches):
            x, cache, st = layer(x, cache, position_offset, lengths)
            new_caches.append(cache)
            if st is not None:
                stats = st._value if stats is None else stats + st._value
        return self.norm(x), new_caches, stats


class MiMoForCausalLM(Layer):
    def __init__(self, config: MiMoConfig):
        super().__init__()
        self.config = config
        self.model = MiMoModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=True)

    def forward(self, input_ids, caches=None, position_offset=0,
                lengths=None):
        """Logits [B, S, vocab]; with `caches`, also the state after the
        window (`lengths` [B] real tokens a row; default all S).  Without,
        the whole sequence from an empty state."""
        b, s = input_ids.shape[0], input_ids.shape[1]
        fresh = caches is None
        if fresh:
            caches = self.init_kv_caches(b, s)
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        h, new_caches, _ = self.model(input_ids, caches, position_offset,
                                      jnp.asarray(_val(lengths), jnp.int32))
        logits = self.lm_head(h)
        return logits if fresh else (logits, new_caches)

    def _expert_layers(self) -> int:
        return sum(l.is_moe for l in self.model.layers)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """A (K, V) pair a layer as (position, KV head) rows, the slot axis
        first: `max_len` positions for a full layer, `sliding_window` for a
        window layer's ring; shapes in the module docstring."""
        cfg = self.config
        dt = dtype or self.lm_head.weight.dtype
        lk, lv = cache_lanes(cfg.head_dim), cache_lanes(cfg.v_head_dim)

        def pair(window: bool):
            hkv = cfg.swa_num_key_value_heads if window \
                else cfg.num_key_value_heads
            rows = (cfg.sliding_window if window else max_len) * hkv
            return (Tensor(jnp.zeros((batch_size, rows, lk), dt)),
                    Tensor(jnp.zeros((batch_size, rows, lv), dt)))
        return [pair(cfg.is_window(i)) for i in range(cfg.num_hidden_layers)]

    def cache_kinds(self):
        """The kind of every leaf of `init_kv_caches`, in its structure:
        "kv" grows a row block a position; "window" is a ring, a fixed cost a
        slot that holds the last `sliding_window` positions only, so a
        prefix's pages cannot stand for it, a rejected draft cannot be taken
        back out of it and a prefill cannot be cut into windows over it."""
        return [("window", "window") if self.config.is_window(i)
                else ("kv", "kv")
                for i in range(self.config.num_hidden_layers)]

    step_name = "mimo"

    @property
    def step_counters(self):
        """What the slot step counts on the device, in the order of the
        int32 vector it returns after its other outputs: (name, entries).
        The engine adds the vectors up and `info()` reports them by name."""
        if not self._expert_layers():
            return ()
        count = (self.config.held_experts or
                 (0, self.config.n_routed_experts))[1]
        return (("moe_steps", 1),) + tuple(
            (n, 1) for n in COUNTER_NAMES[:-1]) + ((COUNTER_NAMES[-1], count),)

    def slot_step_body(self, tok, caches, off, last_pos,
                       return_logits=False):
        """The serving engine's batch-slot step (`models/steps.py` holds the
        contract): `last_pos` [B] is the last REAL token of each row's
        window, so `last_pos + 1` is the length a ring keeps.  After its
        other outputs it returns the expert layers' counters summed
        (`step_counters`).  No window body: `cache_kinds()` says why."""
        h, new_caches, stats = self.model(tok, caches, off, last_pos + 1)
        hv = h._value
        w = self.lm_head.weight._value.T
        logits = tied_lm_head(hv[jnp.arange(hv.shape[0]), last_pos], w)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs = (nxt, logits) if return_logits else (nxt,)
        if stats is not None:
            outs += (jnp.concatenate([jnp.ones((1,), jnp.int32), stats]),)
        return outs, new_caches
