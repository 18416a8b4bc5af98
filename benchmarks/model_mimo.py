"""Builds the program's MiMo-V2-Flash model for a cell and fills it with
seeded weights, as `model.py` does for the Llama family: made on the device
from --seed, one jitted program a kind of layer, the model put together a
layer at a time in the type it runs in, the program's host-side initialiser
switched off meanwhile.

What is drawn how is the configuration file's `assumed.weights`: matrices
normal with the Xavier standard deviation of their [in, out] (a stack of
experts: of one expert's matrix), norm weights 1, the router's weight and its
selection-only bias float32 (bias normal, std 0.02: of the size of the gaps
between neighbouring scores near the top, so a selection that ignored it
would differ), a window layer's sink logits float32, normal round 3 with
std 1 (a sink that takes a few tenths of a head's weight, as trained ones
do: leaving it out changes the layer's output by tens of percent).
"""
from __future__ import annotations

import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp

from .model import weights_of  # noqa: F401  (a mode module takes it from here)

FLOAT32 = ("mlp.router_weight", "mlp.router_bias", "self_attn.sink")


def mimo_config(cfg: dict, depth: int, **over):
    """The program's MiMoConfig for `depth` layers of the file's (cut)
    patterns; the router is as wide as the PUBLISHED expert count and the
    layer holds `held_experts` = [first, count] of them."""
    from paddle_tpu.models.mimo import MiMoConfig
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "v_head_dim",
            "swa_num_attention_heads", "swa_num_key_value_heads",
            "swa_head_dim", "swa_v_head_dim", "sliding_window", "rope_theta",
            "swa_rope_theta", "partial_rotary_factor",
            "attention_value_scale", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "num_experts_per_tok",
            "norm_topk_prob", "scoring_func", "routed_scaling_factor",
            "n_group", "topk_group", "layernorm_epsilon",
            "max_position_embeddings", "tie_word_embeddings")
    kw = {k: cfg[k] for k in same}
    kw.update(num_hidden_layers=depth,
              hybrid_layer_pattern=list(cfg["hybrid_layer_pattern"]),
              moe_layer_freq=list(cfg["moe_layer_freq"]),
              n_routed_experts=cfg["n_routed_experts_published"],
              held_experts=tuple(cfg["held_experts"]))
    kw.update(over)
    return MiMoConfig(**kw)


def _draw(name: str, shape: tuple, key):
    """One parameter in float32, by the rule its name selects."""
    normal = lambda std, mean=0.0: mean + std * jax.random.normal(
        key, shape, jnp.float32)
    if name.endswith("norm.weight"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("router_bias"):
        return normal(0.02)
    if name.endswith("self_attn.sink"):
        return normal(1.0, 3.0)
    if len(shape) not in (2, 3):
        raise RuntimeError(f"no rule to draw {name} {shape}")
    fan_in, fan_out = shape[-2], shape[-1]
    if name.endswith("gate_up_proj"):
        fan_out //= 2                      # gate and up side by side
    return normal(math.sqrt(2.0 / (fan_in + fan_out)))


@functools.lru_cache(maxsize=None)
def _seeded(shape_items: tuple, dtype_name: str):
    """A jitted (key) -> {name: array} for ((name, shape), ...): one program
    a kind of layer."""
    dtype = jnp.dtype(dtype_name)

    def make(key):
        return {n: _draw(n, shp, jax.random.fold_in(key, i)).astype(
            jnp.float32 if n.endswith(FLOAT32) else dtype)
            for i, (n, shp) in enumerate(shape_items)}
    return jax.jit(make)


def fill(layer, key, dtype):
    """`layer` cast to `dtype` (the router's two parameters and the sinks
    stay float32) with its parameters drawn from `key`."""
    layer.astype(dtype)
    params = dict(layer.named_parameters())
    for n, p in params.items():
        if n.endswith(FLOAT32):
            p._set_value(p._value.astype(jnp.float32))
    vals = _seeded(
        tuple((n, tuple(p.shape)) for n, p in sorted(params.items())),
        jnp.dtype(dtype).name)(key)
    missing, unexpected = layer.set_state_dict(vals)
    if missing or unexpected:
        raise RuntimeError(f"seeded weights do not fit: {missing} "
                           f"{unexpected}")


def build_model(cfg: dict, depth: int, seed: int, dtype, **over):
    """MiMoForCausalLM at `depth` layers in `dtype`, weights from `seed`
    (layer i's from fold_in(key, i + 1), whatever the depth)."""
    import paddle_tpu as P
    from paddle_tpu.models.mimo import MiMoDecoderLayer, MiMoForCausalLM
    from paddle_tpu.nn import initializer

    P.seed(seed % (2 ** 31))
    key = jax.random.key(seed % (2 ** 63), impl="threefry2x32")
    mcfg = mimo_config(cfg, 0, **over)
    keep = lambda self, param: param
    with mock.patch.object(initializer.XavierNormal, "__call__", keep), \
            mock.patch.object(initializer.Normal, "__call__", keep):
        model = MiMoForCausalLM(mcfg)
        fill(model, jax.random.fold_in(key, 0), dtype)
        for i in range(depth):
            layer = MiMoDecoderLayer(mcfg, mcfg.is_window(i), mcfg.is_moe(i))
            fill(layer, jax.random.fold_in(key, i + 1), dtype)
            model.model.layers.append(layer)
    mcfg.num_hidden_layers = depth
    return model
