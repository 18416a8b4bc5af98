"""Builds the program's Jamba model for a cell and fills it with seeded
weights, as `model.py` does for the Llama family: made on the device from
--seed, one jitted program a kind of layer, the model put together a layer
at a time in the type it runs in, the program's host-side initialiser
switched off meanwhile.

What is drawn how is the configuration file's `assumed.weights`: matrices
normal with the Xavier standard deviation, norm weights 1, and the Mamba
mixer's own parameters where the published initialiser puts them, so that
dt and exp(dt A) are not at a degenerate 0 or 1.
"""
from __future__ import annotations

import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp

from .model import weights_of  # noqa: F401  (a mode module takes it from here)


def jamba_config(cfg: dict, depth: int):
    from paddle_tpu.models.jamba import JambaConfig
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "attn_layer_period",
            "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
            "mamba_dt_rank", "mamba_expand", "mamba_conv_bias",
            "mamba_proj_bias", "num_experts", "max_position_embeddings",
            "rms_norm_eps", "tie_word_embeddings")
    return JambaConfig(num_hidden_layers=depth, **{k: cfg[k] for k in keys})


def _draw(name: str, shape: tuple, key):
    """One parameter in float32, by the rule its name selects."""
    uniform = lambda lim: jax.random.uniform(key, shape, jnp.float32,
                                             -lim, lim)
    if name.endswith("layernorm.weight") or name.endswith(".D"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("conv1d_weight"):
        return uniform(shape[1] ** -0.5)                  # [d_inner, d_conv]
    if name.endswith("conv1d_bias"):
        return uniform(4 ** -0.5)
    if name.endswith("dt_proj.weight"):
        return uniform(shape[0] ** -0.5)                  # [dt_rank, d_inner]
    if name.endswith("dt_proj.bias"):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return jnp.log(jnp.expm1(dt))                     # softplus^-1(dt)
    if name.endswith("A_log"):
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if len(shape) != 2:
        raise RuntimeError(f"no rule to draw {name} {shape}")
    return math.sqrt(2.0 / (shape[0] + shape[1])) * jax.random.normal(
        key, shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def _seeded(shape_items: tuple, dtype_name: str):
    """A jitted (key) -> {name: array} for ((name, shape), ...): one program
    a kind of layer, so the 26 Mamba layers share one."""
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda key: {
        n: _draw(n, shp, jax.random.fold_in(key, i)).astype(dtype)
        for i, (n, shp) in enumerate(shape_items)})


def build_model(cfg: dict, depth: int, seed: int, dtype):
    """JambaForCausalLM at `depth` layers in `dtype`, weights from `seed`."""
    import paddle_tpu as P
    from paddle_tpu.models.jamba import JambaDecoderLayer, JambaForCausalLM
    from paddle_tpu.nn import initializer

    P.seed(seed % (2 ** 31))
    key = jax.random.key(seed % (2 ** 63), impl="threefry2x32")
    jcfg = jamba_config(cfg, 0)

    def fill(layer, k):
        layer.astype(dtype)
        params = dict(layer.named_parameters())
        vals = _seeded(
            tuple((n, tuple(p.shape)) for n, p in sorted(params.items())),
            jnp.dtype(dtype).name)(k)
        missing, unexpected = layer.set_state_dict(vals)
        if missing or unexpected:
            raise RuntimeError(f"seeded weights do not fit: {missing} "
                               f"{unexpected}")

    with mock.patch.object(initializer.XavierNormal, "__call__",
                           lambda self, param: param):
        model = JambaForCausalLM(jcfg)
        fill(model, jax.random.fold_in(key, 0))
        for i in range(depth):
            layer = JambaDecoderLayer(jcfg, jcfg.is_attention(i))
            fill(layer, jax.random.fold_in(key, i + 1))
            model.model.layers.append(layer)
    jcfg.num_hidden_layers = depth
    return model
