"""Builds the program's model for a cell and fills it with seeded weights.

The weights are the benchmark's data, made on the device from --seed: one
jitted program for a decoder layer, dispatched once a layer, and one for the
rest.  The program would draw them with numpy on the host and copy them
(seconds a layer at these widths) and would first allocate every parameter
as float32 zeros (15 GB for the 16-layer serving cut), so the model is put
together a layer at a time, in the type it runs in, with the program's
host-side initialiser switched off meanwhile.  PERF.md lists both as what
only the program can shorten.
"""
from __future__ import annotations

import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp


def llama_config(cfg: dict, depth: int):
    from paddle_tpu.models import LlamaConfig
    if cfg["hidden_size"] // cfg["num_attention_heads"] != cfg["head_dim"]:
        raise SystemExit("the program derives the head size from hidden / "
                         "heads; this configuration's head_dim differs")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=depth,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"])


@functools.lru_cache(maxsize=None)
def _seeded(shape_items: tuple, dtype_name: str):
    """A jitted (key) -> {name: array} for ((name, shape), ...): matrices
    normal with the Xavier standard deviation, vectors (norm weights) ones.
    One program a distinct set of shapes, so every layer shares one."""
    dtype = jnp.dtype(dtype_name)

    def make(key):
        out = {}
        for i, (n, shp) in enumerate(shape_items):
            if len(shp) == 1:
                out[n] = jnp.ones(shp, dtype)
            else:
                std = math.sqrt(2.0 / (shp[0] + shp[1]))
                out[n] = (std * jax.random.normal(
                    jax.random.fold_in(key, i), shp, jnp.float32)
                ).astype(dtype)
        return out
    return jax.jit(make)


def build_model(cfg: dict, depth: int, seed: int, dtype):
    """LlamaForCausalLM at `depth` layers in `dtype`, weights from `seed`."""
    import paddle_tpu as P
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaDecoderLayer
    from paddle_tpu.nn import initializer

    P.seed(seed % (2 ** 31))
    key = jax.random.key(seed % (2 ** 63), impl="threefry2x32")
    lcfg = llama_config(cfg, 0)

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
        model = LlamaForCausalLM(lcfg)
        fill(model, jax.random.fold_in(key, 0))
        for i in range(depth):
            layer = LlamaDecoderLayer(lcfg)
            fill(layer, jax.random.fold_in(key, i + 1))
            model.llama.layers.append(layer)
    lcfg.num_hidden_layers = depth
    return model


def weights_of(model) -> dict:
    """The model's weights by the program's names, as the reference takes
    them (device arrays, no copy)."""
    return {n: p._value for n, p in model.named_parameters()}
