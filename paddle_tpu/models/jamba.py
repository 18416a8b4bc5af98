"""Jamba: Mamba layers beside attention in one decoder (AI21's hybrid; the
published `config.json` keys are `JambaConfig`'s fields).

Every layer is `x = x + mixer(norm(x)); x = x + mlp(pre_ff_norm(x))`; the
mixer is attention where `i % attn_layer_period == attn_layer_offset` and a
Mamba-1 block everywhere else, the feed-forward the dense SwiGLU MLP
(`LlamaMLP`; `num_experts` 1, the sparse-expert variant is not built).  No
positional embedding of any kind: the recurrence carries order.

Mamba mixer (`d_inner = mamba_expand * hidden`):

    [u, z] = in_proj(x)
    u = silu(causal_depthwise_conv1d(u, k = mamba_d_conv) + conv_bias)
    [dt_r, B, C] = x_proj(u);  each through its own RMSNorm (Jamba's addition)
    dt = softplus(dt_proj(dt_r));  A = -exp(A_log)
    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * u_t) (x) B_t
    y_t = h_t . C_t + D * u_t;  out = out_proj(y * silu(z))

The recurrence runs in float32 and its state is kept in `ssm_state_dtype`
(float32); everything else follows the weights' type.

A request's state is of two kinds, and `init_kv_caches` hands the serving
engine both as one pytree, a pair a layer, every leaf with the slot axis
first (`cache_kinds()` names each leaf's kind):

- attention layer: K and V `[B, S_max, H_kv, D]`, or `[B, S_max, D]` at one
  KV head (`ops/pallas/decode_attention.py mqa_decode_attention` says why);
- Mamba layer: the conv window, the last `d_conv - 1` inputs flattened to
  `[B, (d_conv - 1) * d_inner]`, and the SSM state `[B, d_state, d_inner]`
  (d_inner minor: whole tiles; `[.., d_inner, 16]` would pad eightfold).

A step takes `lengths` [B]: the REAL tokens of each row's window.  Attention
does not care about right padding (a causal mask, and the cache rows past the
prompt are overwritten by decoding), a recurrence does: positions at or
beyond the length leave the SSM state as it was (dt = 0) and the conv window
is the last `d_conv - 1` real inputs, so a padded prefill returns the state
AT the length.  One position (decode) updates the state in plain `jax.numpy`
inside the captured step; a window runs `ops/pallas/selective_scan.py`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..nn.initializer import Assign, Constant, Uniform
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops import manip
from ..ops.dispatch import apply
from .llama import LlamaMLP


@dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        if self.num_experts != 1:
            raise NotImplementedError(
                f"num_experts={self.num_experts}: only the dense feed-forward "
                "(num_experts 1) is built")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    @staticmethod
    def tiny(vocab=128, hidden=64, layers=8, heads=4, kv_heads=1, inter=128,
             period=4, offset=2, d_state=8, dt_rank=8, seq=64):
        return JambaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, attn_layer_period=period,
            attn_layer_offset=offset, mamba_d_state=d_state,
            mamba_dt_rank=dt_rank, max_position_embeddings=seq)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def tied_lm_head(h, w):
    """h [..., hidden] on the embedding [vocab, hidden], in float32 from the
    matmul's accumulator."""
    return jnp.einsum("...h,vh->...v", h.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def mamba_conv1d(xz, win, w, bias, lens):
    """The mixer's causal depthwise conv over a window of positions.
    xz [B, S, 2 * Di] (in_proj's output: u, then the gate z); win
    [B, (k - 1) * Di], the last k - 1 inputs before the window; w [Di, k];
    bias [Di]; lens [B], the real positions of each row.  Returns
    silu(conv(u) + bias), z, and the window after each row's last REAL
    input (right padding does not enter it)."""
    di, k = w.shape
    u = xz[..., :di]
    b, s = u.shape[0], u.shape[1]
    ext = jnp.concatenate(
        [win.reshape(b, k - 1, di).astype(u.dtype), u], axis=1)
    acc = bias.astype(jnp.float32)
    for j in range(k):
        acc = acc + w[:, j].astype(jnp.float32) \
            * ext[:, j:j + s].astype(jnp.float32)
    # input t sits at ext[t + k - 1]: the last k - 1 real ones
    new_win = jax.vmap(lambda e, l: jax.lax.dynamic_slice(
        e, (l, jnp.zeros_like(l)), (k - 1, di)))(ext, lens)
    return (jax.nn.silu(acc).astype(u.dtype), xz[..., di:],
            new_win.reshape(b, -1).astype(win.dtype))


def jamba_attention(q, kn, vn, kc, vc, off, *, num_kv_heads):
    """Causal softmax attention of a window over a cache, no rotation.
    q [B, S, H, D]; kn, vn the window's new keys and values [B, S, H_kv * D];
    kc, vc the cache, [B, S_max, H_kv, D] or [B, S_max, D] at one KV head;
    off [B], where each row's window starts.  Writes the window into the
    cache, then attends: one position through the decode kernel of the
    cache's layout (where it reads that cache in place), a longer window
    over the cache's prefix under a mask.
    Returns the output [B, S, H, D] and both caches."""
    b, s, nh, d = q.shape
    hkv = num_kv_heads

    def put(c, n, o):
        return jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (o,) + (jnp.zeros_like(o),) * (c.ndim - 1))
    shape = (b, s) + kc.shape[2:]
    kc = jax.vmap(put)(kc, kn.reshape(shape), off)
    vc = jax.vmap(put)(vc, vn.reshape(shape), off)
    if s == 1:
        from ..ops.pallas.decode_attention import (
            mqa_decode_attention, ragged_decode_attention, reads_in_place)
        if kc.ndim == 3:
            return mqa_decode_attention(q, kc, vc, off + 1), kc, vc
        if reads_in_place(kc.shape, vc.shape):
            return ragged_decode_attention(q, kc, vc, off + 1), kc, vc
    # a window: positions off .. off + s - 1 over the cache's prefix (and one
    # position over a cache whose lanes the ragged kernel refuses)
    k4 = kc.reshape(b, kc.shape[1], hkv, d)
    v4 = vc.reshape(b, vc.shape[1], hkv, d)
    qg = q.reshape(b, s, hkv, nh // hkv, d)
    sc = jnp.einsum("bsgnd,btgd->bgnst", qg, k4,
                    preferred_element_type=jnp.float32) / math.sqrt(d)
    j = jnp.arange(kc.shape[1])[None, None, :]
    i = jnp.arange(s)[None, :, None] + off.reshape(-1, 1, 1)
    sc = jnp.where((j <= i)[:, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1).astype(v4.dtype)
    out = jnp.einsum("bgnst,btgd->bsgnd", p, v4,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, nh, d).astype(q.dtype), kc, vc


class JambaMambaMixer(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        h, di = config.hidden_size, config.d_inner
        n, k, r = (config.mamba_d_state, config.mamba_d_conv,
                   config.mamba_dt_rank)
        bias = config.mamba_proj_bias
        self.in_proj = ColumnParallelLinear(h, 2 * di, has_bias=bias,
                                            gather_output=False)
        # depthwise: one k-tap filter a channel ([d_inner, 1, k] published)
        self.conv1d_weight = self.create_parameter(
            [di, k], default_initializer=Uniform(-k ** -0.5, k ** -0.5))
        self.conv1d_bias = self.create_parameter(
            [di], is_bias=True,
            default_initializer=Uniform(-k ** -0.5, k ** -0.5)
            if config.mamba_conv_bias else Constant(0.0))
        self.x_proj = RowParallelLinear(di, r + 2 * n, has_bias=False,
                                        input_is_parallel=True)
        self.dt_layernorm = RMSNorm(r, config.rms_norm_eps)
        self.b_layernorm = RMSNorm(n, config.rms_norm_eps)
        self.c_layernorm = RMSNorm(n, config.rms_norm_eps)
        self.dt_proj = ColumnParallelLinear(r, di, has_bias=True,
                                            gather_output=False)
        # the published initialiser: dt log-uniform in [1e-3, 1e-1] through
        # the bias, A = -(1..d_state) a channel, D = 1
        Uniform(-r ** -0.5, r ** -0.5)(self.dt_proj.weight)
        dt = np.exp(np.random.RandomState(di).uniform(
            math.log(1e-3), math.log(1e-1), di))
        Assign(np.log(np.expm1(dt)))(self.dt_proj.bias)
        self.A_log = self.create_parameter(
            [di, n], default_initializer=Assign(np.log(np.broadcast_to(
                np.arange(1, n + 1, dtype=np.float32), (di, n)))))
        self.D = self.create_parameter([di],
                                       default_initializer=Constant(1.0))
        self.out_proj = RowParallelLinear(di, h, has_bias=bias,
                                          input_is_parallel=True)

    def forward(self, x, state, lengths):
        """x [B, S, hidden]; state (conv window, SSM state); lengths [B].
        Returns the mixer's output and the state at each row's length."""
        cfg = self.config
        conv_state, ssm_state = state
        xz = self.in_proj(x)
        u, z, new_conv = apply(mamba_conv1d, xz, conv_state,
                               self.conv1d_weight, self.conv1d_bias, lengths,
                               op_name="mamba_conv1d")
        r, n = cfg.mamba_dt_rank, cfg.mamba_d_state
        # float32 from the projection's accumulator on: dt, B and C are what
        # the recurrence multiplies into its state step after step, and a
        # bfloat16 rounding of them is the error it keeps
        dbc = self.x_proj(u.astype("float32"))
        dt = self.dt_proj(self.dt_layernorm(dbc[..., :r]))
        b_in = self.b_layernorm(dbc[..., r:r + n])
        c_in = self.c_layernorm(dbc[..., r + n:])

        def scan(u_, dt_, b_, c_, z_, a_log, d_, h0, lens):
            f32 = jnp.float32
            a = -jnp.exp(a_log.astype(f32))                     # [Di, N]
            dt_ = jax.nn.softplus(dt_.astype(f32))
            if u_.shape[1] == 1:
                # one position a slot (its length is 1): a fusion over the
                # donated state
                dt1, u1 = dt_[:, 0], u_[:, 0].astype(f32)         # [B, Di]
                h = jnp.exp(dt1[:, None, :] * a.T[None]) * h0.astype(f32) \
                    + (dt1 * u1)[:, None, :] * b_[:, 0].astype(f32)[:, :, None]
                y = jnp.sum(h * c_[:, 0].astype(f32)[:, :, None], axis=1) \
                    + d_.astype(f32) * u1
                zf = z_[:, 0].astype(f32)
                y = (y * zf * jax.nn.sigmoid(zf)).astype(u_.dtype)[:, None]
            else:
                from ..ops.pallas.selective_scan import selective_scan
                y, h = selective_scan(u_, dt_, a, b_, c_, d_, z_,
                                      h0.astype(f32), lens)
            return y, h.astype(h0.dtype)

        y, new_ssm = apply(scan, u, dt, b_in, c_in, z, self.A_log, self.D,
                           ssm_state, lengths, op_name="selective_scan")
        return self.out_proj(y), (new_conv, new_ssm)


class JambaAttention(Layer):
    """Causal softmax attention at 1/sqrt(head), no rotation, over a cache;
    at one KV head the cache's head axis is folded away."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        kv_out = self.num_kv_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kv_out, has_bias=False,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv_out, has_bias=False,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=False,
                                        input_is_parallel=True)

    def forward(self, x, kv_cache, position_offset):
        b, s = x.shape[0], x.shape[1]
        nh, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = manip.reshape(self.q_proj(x), [b, s, nh, d])
        off = jnp.broadcast_to(jnp.asarray(_val(position_offset), jnp.int32),
                               (b,))
        attn, kc, vc = apply(jamba_attention, q, self.k_proj(x),
                             self.v_proj(x), kv_cache[0], kv_cache[1], off,
                             op_name="jamba_attention", num_kv_heads=hkv)
        return self.o_proj(manip.reshape(attn, [b, s, nh * d])), (kc, vc)


class JambaDecoderLayer(Layer):
    def __init__(self, config: JambaConfig, attention: bool):
        super().__init__()
        self.is_attention = attention
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        if attention:
            self.self_attn = JambaAttention(config)
        else:
            self.mamba = JambaMambaMixer(config)
        self.pre_ff_layernorm = RMSNorm(config.hidden_size,
                                        config.rms_norm_eps)
        self.feed_forward = LlamaMLP(config)

    def forward(self, x, cache, position_offset, lengths):
        y = self.input_layernorm(x)
        if self.is_attention:
            mixed, cache = self.self_attn(y, cache, position_offset)
        else:
            mixed, cache = self.mamba(y, cache, lengths)
        h = x + mixed
        return h + self.feed_forward(self.pre_ff_layernorm(h)), cache


class JambaModel(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([
            JambaDecoderLayer(config, config.is_attention(i))
            for i in range(config.num_hidden_layers)])
        self.final_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)

    def forward(self, input_ids, caches, position_offset, lengths):
        x = self.embed_tokens(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            x, cache = layer(x, cache, position_offset, lengths)
            new_caches.append(cache)
        return self.final_layernorm(x), new_caches


class JambaForCausalLM(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.model = JambaModel(config)
        self.lm_head = None if config.tie_word_embeddings else \
            ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                 has_bias=False, gather_output=True)

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
        h, new_caches = self.model(input_ids, caches, position_offset,
                                   jnp.asarray(_val(lengths), jnp.int32))
        logits = self.head(h)
        return logits if fresh else (logits, new_caches)

    def head(self, h):
        """Logits of hidden states [..., hidden]: the embedding's transpose
        when the head is tied."""
        if self.lm_head is not None:
            return self.lm_head(h)
        return apply(tied_lm_head, h, self.model.embed_tokens.weight,
                     op_name="tied_lm_head")

    def _last_logits(self, h, last_pos):
        """Float32 logits [B, vocab] of each row's position `last_pos` (the
        head on those rows only, not on a prefill's whole bucket) and their
        argmax; float32 from the matmul's accumulator, so that a near-tie
        is not decided by a rounding to 8 bits."""
        w = self.lm_head.weight._value.T if self.lm_head is not None \
            else self.model.embed_tokens.weight._value
        logits = tied_lm_head(h[jnp.arange(h.shape[0]), last_pos], w)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """A pair a layer, the slot axis first in every leaf: (K, V) for an
        attention layer, (conv window, SSM state) for a Mamba layer; shapes
        in the module docstring."""
        cfg = self.config
        dt = dtype or self.model.embed_tokens.weight.dtype
        d = cfg.hidden_size // cfg.num_attention_heads
        hkv = cfg.num_key_value_heads
        kv = (batch_size, max_len) + ((d,) if hkv == 1 else (hkv, d))
        conv = (batch_size, (cfg.mamba_d_conv - 1) * cfg.d_inner)
        ssm = (batch_size, cfg.mamba_d_state, cfg.d_inner)
        zeros = lambda shape, t: Tensor(jnp.zeros(shape, t))
        return [(zeros(kv, dt), zeros(kv, dt)) if cfg.is_attention(i)
                else (zeros(conv, dt), zeros(ssm, cfg.ssm_state_dtype))
                for i in range(cfg.num_hidden_layers)]

    def cache_kinds(self):
        """The kind of every leaf of `init_kv_caches`, in its structure:
        "kv" grows a row a position, "state" is a fixed cost a slot that
        cannot be rewound, shared by prefix or cut into chunks."""
        return [("kv", "kv") if self.config.is_attention(i)
                else ("state", "state")
                for i in range(self.config.num_hidden_layers)]

    step_name = "jamba"

    def slot_step_body(self, tok, caches, off, last_pos,
                       return_logits=False):
        """The serving engine's batch-slot step (`models/steps.py` holds the
        contract): `last_pos` [B] is the last REAL token of each row's
        window, so `last_pos + 1` is the length the recurrent layers stop
        at.  No window body: `cache_kinds()` says why."""
        h, new_caches = self.model(tok, caches, off, last_pos + 1)
        nxt, last = self._last_logits(h._value, last_pos)
        return ((nxt, last) if return_logits else (nxt,)), new_caches
