"""The plain reference of the MiMo-V2-Flash decoder (window and full
attention mixed, routed experts): float32 `jax.numpy`, full matmul
precision, a blocked masked softmax, the held experts one at a time over
every position, no kernels, no cache, no padding, no batching.

It follows the published block, `h = x + Attn(RMSNorm(x)); y = h +
FFN(RMSNorm(h))`, a final RMSNorm and an untied head, with the equations of
ISSUE 32 (PERF.md, section 4):

- attention: q as H heads of `head_dim`, k as H_kv heads of `head_dim`, v as
  H_kv heads of `v_head_dim` times `attention_value_scale`; the leading
  `int(head_dim * partial_rotary_factor)` lanes of q and k rotate, half-split
  pairs; scores over sqrt(head_dim); query head h reads KV head
  h // (H / H_kv).  A full layer (`hybrid_layer_pattern` 0): `rope_theta`, a
  causal mask.  A window layer (1): `swa_rope_theta`, key j visible to query
  i iff 0 <= i - j < sliding_window, and one learned logit a query head that
  joins the softmax's denominator and carries no value;
- feed-forward: the dense SwiGLU where `moe_layer_freq` is 0; else
  s = sigmoid(x W_r) in float32, sel = top-k of s + b, w = s over its sum on
  sel, y = sum of w_i E_i(x) over the selected experts THIS SHARE HOLDS
  (`held_experts` = (first, count); w is normalised over all the selected).

Weights come in as a dict by the program's parameter names
(`model.layers.<i>.self_attn.q_proj.weight`, `model.layers.<i>.mlp
.gate_up_proj` [count, hidden, 2 x width], ...), matrices [in, out].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .reference import F32, _exact, rms_norm

Q_BLOCK = 128       # query rows a block: bounds the score tensor's size
TRIM = 1024         # a sequence is cut to the positions asked for, in these


def partial_rope(x, theta, rotary_dim):
    """x [S, H, D] at positions 0..S-1: lanes [0, rotary_dim) rotate, lane i
    with lane i + rotary_dim / 2; the rest pass."""
    s, half = x.shape[0], rotary_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                           / rotary_dim))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # [S, r/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def masked_attention(q, k, v, window=None, sink=None):
    """q [S, H, D], k [S, Hkv, D], v [S, Hkv, Dv]; softmax over the keys
    j <= i, and i - j < window where a window is given; sink [H] joins the
    denominator.  A block of queries at a time."""
    s, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    blk = min(Q_BLOCK, s)
    pad = (-s) % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, h, d)
    starts = jnp.arange(qp.shape[0]) * blk
    keys = jnp.arange(s)

    def one(args):
        qb, start = args
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(d))
        rows = (start + jnp.arange(blk))[None, :, None]
        seen = keys[None, None, :] <= rows
        if window is not None:
            seen = seen & (rows - keys[None, None, :] < window)
        sc = jnp.where(seen, sc, -jnp.inf)
        m = jnp.max(sc, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink[:, None, None])
        e = jnp.exp(sc - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(sink[:, None, None] - m)
        return jnp.einsum("hqk,khd->qhd", e / den, v)

    out = jax.lax.map(one, (qp, starts))
    return out.reshape(-1, h, v.shape[-1])[:s]


def attention(p, y, cfg, window: bool):
    h, d, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                cfg["v_head_dim"])
    s = y.shape[0]
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    rot = int(d * cfg["partial_rotary_factor"])
    q = (y @ p["self_attn.q_proj.weight"]).reshape(s, h, d)
    k = (y @ p["self_attn.k_proj.weight"]).reshape(s, -1, d)
    v = cfg["attention_value_scale"] * (
        y @ p["self_attn.v_proj.weight"]).reshape(s, -1, dv)
    k = partial_rope(k, theta, rot)
    a = masked_attention(
        partial_rope(q, theta, rot), k, v,
        cfg["sliding_window"] if window else None,
        p.get("self_attn.sink") if window else None)
    return a.reshape(s, h * dv) @ p["self_attn.o_proj.weight"], k, v


def route(p, y, cfg):
    """(sel [S, k], w [S, k], scores [S, experts]) of the router, float32."""
    s = jax.nn.sigmoid(y @ p["mlp.router_weight"])
    _, sel = jax.lax.top_k(s + p["mlp.router_bias"],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, sel, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return sel, w * (cfg.get("routed_scaling_factor") or 1.0), s


def expert_ffn(p, y, cfg, first: int):
    """This share of the routed layer on y [S, hidden]: held expert e is
    local index e - first of the stacked weights; an expert at a time over
    every position, weighted where it was selected and by zero elsewhere."""
    sel, w, _ = route(p, y, cfg)
    gate_up, down = p["mlp.gate_up_proj"], p["mlp.down_proj"]
    width = down.shape[1]

    def one(acc, args):
        e, gu, dn = args
        we = jnp.sum(jnp.where(sel == e, w, 0.0), axis=1)       # [S]
        hid = y @ gu
        out = (jax.nn.silu(hid[:, :width]) * hid[:, width:]) @ dn
        return acc + we[:, None] * out, None

    held = first + jnp.arange(gate_up.shape[0])
    acc, _ = jax.lax.scan(one, jnp.zeros_like(y), (held, gate_up, down))
    return acc


def dense_ffn(p, y):
    gate = jax.nn.silu(y @ p["mlp.gate_proj.weight"])
    return (gate * (y @ p["mlp.up_proj.weight"])) @ p["mlp.down_proj.weight"]


def decoder_layer(p, x, cfg, window: bool, first: int):
    """One block on x [S, hidden]; `p` holds this layer's weights by their
    names inside the layer, any float type; the feed-forward's kind is read
    off them.  Returns the output and, for the checks of single layers, the
    attention's output before the residual, the keys (rotated) and values
    (scaled) a cache would hold, the feed-forward's normed input and its
    output."""
    p = {k: v.astype(F32) for k, v in p.items()}
    eps = cfg["layernorm_epsilon"]
    a, keys, values = attention(
        p, rms_norm(x, p["input_layernorm.weight"], eps), cfg, window)
    h = x + a
    y = rms_norm(h, p["post_attention_layernorm.weight"], eps)
    ffn = expert_ffn(p, y, cfg, first) if "mlp.router_weight" in p \
        else dense_ffn(p, y)
    return h + ffn, {"attn": a, "k": keys, "v": values, "ffn_in": y,
                     "ffn": ffn}


def split_layers(weights: dict, depth: int):
    """(outer weights, [layer dicts]) from the program's flat names."""
    layers = []
    for i in range(depth):
        pre = f"model.layers.{i}."
        layers.append({k[len(pre):]: v for k, v in weights.items()
                       if k.startswith(pre)})
    outer = {k: v for k, v in weights.items() if ".layers." not in k}
    return outer, layers


def held_first(cfg: dict) -> int:
    held = cfg.get("held_experts")
    return int(held[0]) if held else 0


def _layer_fns(cfg: dict):
    """Jitted pieces, one compile a kind of layer whatever the depth: the
    layers run one call at a time, so only one layer's float32 copy is alive
    at once."""
    first = held_first(cfg)
    embed = jax.jit(lambda w, ids: w.astype(F32)[ids])
    layer = jax.jit(_exact(
        lambda p, x, window: decoder_layer(p, x, cfg, window, first)),
        static_argnums=2)
    return embed, layer


def make_reference(cfg: dict):
    embed, layer = _layer_fns(cfg)
    pattern = cfg["hybrid_layer_pattern"]

    def _head(norm_w, head_w, x):
        return rms_norm(x, norm_w.astype(F32), cfg["layernorm_epsilon"]) \
            @ head_w.astype(F32)

    head = jax.jit(_exact(_head))

    def logits(weights, depth, ids, positions):
        """Logits [len(positions), vocab] of one sequence `ids` [S]; the
        sequence is cut behind the last position asked for (a causal model:
        what follows changes nothing), to whole blocks of TRIM."""
        keep = min(int(ids.shape[0]),
                   -(-(int(np.max(np.asarray(positions))) + 1) // TRIM) * TRIM)
        outer, layers = split_layers(weights, depth)
        x = embed(outer["model.embed_tokens.weight"], ids[:keep])
        for i, p in enumerate(layers):
            x, _ = layer(p, x, bool(pattern[i]))
        return head(outer["model.norm.weight"], outer["lm_head.weight"],
                    x[positions])

    return logits


def make_layer_reference(cfg: dict):
    """For the direct checks of single layers, two functions.
    `layers(weights, depth, ids)`: for each of the first `depth` layers the
    hidden states entering it (`x_in`), its attention's output before the
    residual, the keys and values its cache would hold and its
    feed-forward's normed input.  `experts_on(weights, i, y)`: layer i's
    routed feed-forward on GIVEN inputs y [S, hidden] (the program is handed
    the same, rounded to its type): this share's output, the router's
    selections and the biased scores they were taken from."""
    embed, layer = _layer_fns(cfg)
    pattern, first = cfg["hybrid_layer_pattern"], held_first(cfg)

    @jax.jit
    @_exact
    def routed(p, y):
        p = {k: v.astype(F32) for k, v in p.items() if k.startswith("mlp.")}
        y = y.astype(F32)
        sel, _, s = route(p, y, cfg)
        return {"ffn": expert_ffn(p, y, cfg, first), "sel": sel,
                "biased": s + p["mlp.router_bias"]}

    def layers(weights, depth, ids):
        outer, per_layer = split_layers(weights, depth)
        x = embed(outer["model.embed_tokens.weight"], ids)
        out = []
        for i, p in enumerate(per_layer):
            x_in = x
            x, aux = layer(p, x, bool(pattern[i]))
            out.append(dict(aux, x_in=x_in))
        return out

    def experts_on(weights, i, y):
        return routed(split_layers(weights, i + 1)[1][-1], y)

    return layers, experts_on
