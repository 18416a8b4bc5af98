"""The plain reference of the Jamba decoder (Mamba layers beside attention):
float32 `jax.numpy`, full matmul precision, a `lax.scan` a position for the
recurrence, a blocked causal softmax for the attention layers, no kernels, no
cache, no padding, no batching.

It follows the published block (`transformers` `modeling_jamba.py`, the slow
path): every layer is `x + mixer(norm(x))` then `x + mlp(norm(x))`; the
mixer is attention (no rotary embedding, grouped-query, 1/sqrt(head)) where
`i % attn_layer_period == attn_layer_offset`, else Mamba-1 with Jamba's
RMSNorm on dt, B and C; the head is the embedding's transpose.  Departures,
noted in the configuration's file too: the depthwise filter is stored
[d_inner, k] where the checkpoint has [d_inner, 1, k]; matrices are
[in, out]; `num_experts` is 1, so no router exists.

Weights come in as a dict by the program's parameter names
(`model.layers.<i>.mamba.in_proj.weight`, ...).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import F32, _exact, causal_attention, rms_norm


def mlp(p, x, eps):
    y = rms_norm(x, p["pre_ff_layernorm.weight"], eps)
    gate = jax.nn.silu(y @ p["feed_forward.gate_proj.weight"])
    return x + (gate * (y @ p["feed_forward.up_proj.weight"])) \
        @ p["feed_forward.down_proj.weight"]


def mamba_mixer(p, y, cfg):
    """y [S, hidden], already normed; the state starts at zero.  Returns the
    mixer's output and the SSM state [d_inner, n] after the last position."""
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    n, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps, s = cfg["rms_norm_eps"], y.shape[0]
    xz = y @ p["mamba.in_proj.weight"]
    u, z = xz[:, :di], xz[:, di:]
    past = jnp.pad(u, ((k - 1, 0), (0, 0)))         # zeros before position 0
    w = p["mamba.conv1d_weight"]                    # [d_inner, k]
    u = jax.nn.silu(sum(w[:, j] * past[j:j + s] for j in range(k))
                    + p["mamba.conv1d_bias"])
    dbc = u @ p["mamba.x_proj.weight"]
    dt_r = rms_norm(dbc[:, :r], p["mamba.dt_layernorm.weight"], eps)
    b = rms_norm(dbc[:, r:r + n], p["mamba.b_layernorm.weight"], eps)
    c = rms_norm(dbc[:, r + n:], p["mamba.c_layernorm.weight"], eps)
    dt = jax.nn.softplus(dt_r @ p["mamba.dt_proj.weight"]
                         + p["mamba.dt_proj.bias"])
    a = -jnp.exp(p["mamba.A_log"])                  # [d_inner, n]

    def step(h, xs):
        dt_t, u_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t[None]
        return h, h @ c_t

    h, ys = jax.lax.scan(step, jnp.zeros((di, n), F32), (dt, u, b, c))
    ys = ys + p["mamba.D"] * u
    return (ys * jax.nn.silu(z)) @ p["mamba.out_proj.weight"], h


def attention_mixer(p, y, cfg):
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, s = cfg["hidden_size"] // h, y.shape[0]
    q = (y @ p["self_attn.q_proj.weight"]).reshape(s, h, d)
    k = (y @ p["self_attn.k_proj.weight"]).reshape(s, hkv, d)
    v = (y @ p["self_attn.v_proj.weight"]).reshape(s, hkv, d)
    return causal_attention(q, k, v).reshape(s, h * d) \
        @ p["self_attn.o_proj.weight"], None


def decoder_layer(p, x, cfg):
    """One block on x [S, hidden]; `p` holds this layer's weights by their
    names inside the layer, any float type; its kind is read off them."""
    p = {k: v.astype(F32) for k, v in p.items()}
    y = rms_norm(x, p["input_layernorm.weight"], cfg["rms_norm_eps"])
    mixer = attention_mixer if "self_attn.q_proj.weight" in p else mamba_mixer
    mixed, state = mixer(p, y, cfg)
    return mlp(p, x + mixed, cfg["rms_norm_eps"]), state


def split_layers(weights: dict, depth: int):
    """(outer weights, [layer dicts]) from the program's flat names."""
    layers = []
    for i in range(depth):
        pre = f"model.layers.{i}."
        layers.append({k[len(pre):]: v for k, v in weights.items()
                       if k.startswith(pre)})
    outer = {k: v for k, v in weights.items() if ".layers." not in k}
    return outer, layers


def _layer_fns(cfg: dict):
    """Jitted pieces, one compile a kind of layer whatever the depth: the
    layers run one call at a time, so only one layer's float32 copy is
    alive at once."""
    embed = jax.jit(lambda w, ids: w.astype(F32)[ids])
    layer = jax.jit(_exact(lambda p, x: decoder_layer(p, x, cfg)))
    return embed, layer


def make_reference(cfg: dict):
    embed, layer = _layer_fns(cfg)

    def _head(norm_w, embed_w, x):
        return rms_norm(x, norm_w.astype(F32), cfg["rms_norm_eps"]) \
            @ embed_w.astype(F32).T

    head = jax.jit(_exact(_head))

    def logits(weights, depth, ids, positions):
        """Logits [len(positions), vocab] of one sequence `ids` [S]."""
        outer, layers = split_layers(weights, depth)
        x = embed(outer["model.embed_tokens.weight"], ids)
        for p in layers:
            x, _ = layer(p, x)
        return head(outer["model.final_layernorm.weight"],
                    outer["model.embed_tokens.weight"], x[positions])

    return logits


def make_state_reference(cfg: dict):
    embed, layer = _layer_fns(cfg)

    def first_state(weights, depth, ids):
        """The first Mamba layer's SSM state [d_inner, n] after the whole of
        one sequence `ids` [S]: the layers up to that one, no further."""
        outer, layers = split_layers(weights, depth)
        x = embed(outer["model.embed_tokens.weight"], ids)
        for p in layers:
            x, state = layer(p, x)
            if state is not None:
                return state
        raise ValueError(f"no Mamba layer among the first {depth}")

    return first_state
