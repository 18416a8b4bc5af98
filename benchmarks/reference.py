"""The plain reference of the Llama-family decoder: float32 `jax.numpy`,
full matmul precision, no kernels, no cache, no batching tricks.

It follows the published block (pre-norm RMSNorm, rotary grouped-query
attention with a causal mask, SwiGLU MLP, untied head).  Departures, both
shared by every configuration here and noted in their files: the rotary
embedding rotates interleaved pairs, as the program does (a permutation of
q/k columns against the checkpoints' half-split layout), and q, k, v are
three matrices where InternLM2's checkpoint packs them into one.

Weights come in as a dict by the program's parameter names
(`llama.layers.<i>.self_attn.q_proj.weight`, ...), [in, out] matrices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512       # query rows a block: bounds the score tensor's size


def rms_norm(x, w, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w


def rope(x, theta):
    """x [S, H, D] at positions 0..S-1; pairs (2i, 2i+1) rotate together."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v):
    """q [S, H, D], k and v [S, Hkv, D]; softmax over keys <= the query,
    computed a block of queries at a time."""
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
        rows = start + jnp.arange(blk)
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(one, (qp, starts))
    return out.reshape(-1, h, d)[:s]


def decoder_layer(p, x, cfg):
    """One block on x [S, hidden]; `p` holds this layer's weights by their
    names inside the layer (`self_attn.q_proj.weight`, ...), any float type."""
    p = {k: v.astype(F32) for k, v in p.items()}
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = x.shape[0]
    y = rms_norm(x, p["input_layernorm.weight"], eps)
    q = (y @ p["self_attn.q_proj.weight"]).reshape(s, h, d)
    k = (y @ p["self_attn.k_proj.weight"]).reshape(s, hkv, d)
    v = (y @ p["self_attn.v_proj.weight"]).reshape(s, hkv, d)
    a = causal_attention(rope(q, theta), rope(k, theta), v)
    x = x + a.reshape(s, h * d) @ p["self_attn.o_proj.weight"]
    y = rms_norm(x, p["post_attention_layernorm.weight"], eps)
    gate = jax.nn.silu(y @ p["mlp.gate_proj.weight"])
    return x + (gate * (y @ p["mlp.up_proj.weight"])) @ p["mlp.down_proj.weight"]


def split_layers(weights: dict, depth: int):
    """(outer weights, [layer dicts]) from the program's flat names."""
    layers = []
    for i in range(depth):
        pre = f"llama.layers.{i}."
        layers.append({k[len(pre):]: v for k, v in weights.items()
                       if k.startswith(pre)})
    outer = {k: v for k, v in weights.items() if ".layers." not in k}
    return outer, layers


def _exact(fn):
    def run(*a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)
    return run


def make_reference(cfg: dict):
    """Jitted pieces, one compile each whatever the depth: the layers run
    one call at a time, so only one layer's float32 copy is alive at once."""
    embed = jax.jit(lambda w, ids: w.astype(F32)[ids])
    layer = jax.jit(_exact(lambda p, x: decoder_layer(p, x, cfg)))

    def _head(norm_w, head_w, x):
        return rms_norm(x, norm_w.astype(F32), cfg["rms_norm_eps"]) \
            @ head_w.astype(F32)

    head = jax.jit(_exact(_head))

    def hidden(outer, layers, ids):
        x = embed(outer["llama.embed_tokens.weight"], ids)
        for p in layers:
            x = layer(p, x)
        return x

    def logits(weights, depth, ids, positions):
        """Logits [len(positions), vocab] of one sequence `ids` [S]."""
        outer, layers = split_layers(weights, depth)
        x = hidden(outer, layers, ids)
        return head(outer["llama.norm.weight"], outer["lm_head.weight"],
                    x[positions])

    return logits


def loss_and_embed_grad_norm(cfg: dict, weights: dict, depth: int, ids, labels):
    """Mean next-token cross-entropy over sequences `ids` [B, S] and the
    norm of its gradient with respect to the embedding table (a gradient
    that has passed through every layer's backward)."""
    outer, layers = split_layers(weights, depth)
    embed_w = outer.pop("llama.embed_tokens.weight")

    def loss_fn(embed_w, outer, layers, ids, labels):
        def one(seq):
            x = embed_w.astype(F32)[seq]
            for p in layers:
                x = decoder_layer(p, x, cfg)
            return rms_norm(x, outer["llama.norm.weight"].astype(F32),
                            cfg["rms_norm_eps"]) \
                @ outer["lm_head.weight"].astype(F32)
        lg = jnp.stack([one(s) for s in ids])
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    # the weights are arguments, not constants of the program
    fn = jax.jit(_exact(jax.value_and_grad(loss_fn)))
    loss, g = fn(embed_w, outer, layers, ids, labels)
    return float(loss), float(jnp.sqrt(jnp.sum(jnp.square(g))))
