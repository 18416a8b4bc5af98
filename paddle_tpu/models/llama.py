"""LLaMA model family — the flagship (BASELINE config 5: LLaMA-7B pretrain
under hybrid parallel; reference: PaddleNLP llama + fleet meta_parallel).

Layers use the TP building blocks (VocabParallelEmbedding, Column/Row
ParallelLinear) so one model definition runs:
- single device (specs degrade to no-ops),
- tp/sp via GSPMD sharding constraints over the 'mp' axis,
- dp via batch sharding,
- pp via `build_hybrid_train_step` which stacks decoder-block params on a
  leading stage dim and runs them through parallel/pipeline.spmd_pipeline
  (shard_map + ppermute over the 'pp' axis, manual; dp/mp stay GSPMD-auto).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import generator as gen
from ..core.tensor import Parameter, Tensor
from ..autograd.grad_mode import no_grad
from ..nn import functional as F
from ..nn.layer.common import Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops.dispatch import apply
from ..ops import manip
from ..parallel import mesh as mesh_mod
from ..parallel.pipeline import spmd_pipeline
from .steps import compiled_step
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    owned_spec, shard_constraint_t,
)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    sequence_parallel: bool = False
    # long-context: "ring" (blockwise ppermute ring attention) or "ulysses"
    # (all-to-all head/seq re-shard) over the mesh's 'sep' axis
    context_parallel: Optional[str] = None
    recompute: bool = False

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    @staticmethod
    def llama_7b():
        return LlamaConfig()

    @staticmethod
    def tiny(vocab=128, hidden=64, layers=2, heads=4, inter=128, seq=64):
        return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                           intermediate_size=inter, num_hidden_layers=layers,
                           num_attention_heads=heads,
                           max_position_embeddings=seq)


def _rope(q, k, theta, position_offset=0, rotary_dim=None, half_split=False):
    """Rotary embeddings on [B, S, H, D] (fp32 trig, matches reference
    fused_rotary_position_embedding semantics). position_offset may be a
    traced scalar (the KV-cache decode path) or a [B] vector — the serving
    engine's batch-slot decode, where every slot sits at its own position.
    `rotary_dim` rotates only the leading lanes of every head and passes the
    rest through (partial rotation); `half_split` pairs lane i with lane
    i + rotary_dim / 2 (the GPT-NeoX convention) where the default pairs
    2i with 2i + 1.  q and k may differ in heads."""
    d = q.shape[3] if rotary_dim is None else rotary_dim
    s = q.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    off = jnp.asarray(position_offset, jnp.float32)
    pos = jnp.arange(s, dtype=jnp.float32)[None, :] + off.reshape(-1, 1)
    freqs = pos[:, :, None] * inv[None, None, :]   # [1|B, S, D/2]
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]

    def rot(x):
        if half_split:
            x1 = x[..., :d // 2].astype(jnp.float32)
            x2 = x[..., d // 2:d].astype(jnp.float32)
            out = jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
            return jnp.concatenate([out.astype(x.dtype), x[..., d:]], axis=-1)
        x1 = x[..., 0::2].astype(jnp.float32)
        x2 = x[..., 1::2].astype(jnp.float32)
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
        return out.astype(x.dtype)
    return rot(q), rot(k)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // self.num_heads
        kv_out = self.num_kv_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(h, h, has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(h, kv_out, has_bias=False,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, kv_out, has_bias=False,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(h, h, has_bias=False, input_is_parallel=True)

    def forward(self, x, position_offset=0, kv_cache=None):
        b, s = x.shape[0], x.shape[1]
        q = manip.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = manip.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = manip.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        # The KV decode offset is threaded through apply() as a TRACED i32
        # scalar (not a closure capture), so every decode-step op is keyed
        # only by avals — one compiled-op cache entry serves every token
        # position, and whole-step capture sees the offset as a program
        # input instead of a baked constant.
        off = position_offset._value if isinstance(position_offset, Tensor) \
            else position_offset
        off = jnp.asarray(off, jnp.int32)
        theta = self.config.rope_theta

        def rope_fn(qq, kk, off_):
            return _rope(qq, kk, theta, off_)

        out = apply(rope_fn, q, k, off, op_name="rope")
        q, k = out[0], out[1]
        # heads sharded over mp
        heads = owned_spec(4, 2, "mp")
        q = shard_constraint_t(q, *heads)
        k = shard_constraint_t(k, *heads)
        v = shard_constraint_t(v, *heads)
        if kv_cache is not None:
            # Decode path (FusedMultiTransformer / masked_multihead_attention
            # analog, incubate/nn/layer/fused_transformer.py:1021): write the
            # new K/V into the static-length cache at position_offset and
            # attend over the cache under a length mask — one compiled
            # program per (prefill, decode) shape, O(S) per new token.
            k_cache, v_cache = kv_cache

            def upd(kc, vc, kn, vn, off_):
                if off_.ndim:  # per-slot offsets: one write position per row
                    def one(c, n, o):
                        z = jnp.asarray(0, jnp.int32)
                        return jax.lax.dynamic_update_slice(
                            c, n.astype(c.dtype), (o, z, z))
                    return (jax.vmap(one)(kc, kn, off_),
                            jax.vmap(one)(vc, vn, off_))
                z = jnp.asarray(0, jnp.int32)
                start = (z, off_, z, z)
                return (jax.lax.dynamic_update_slice(kc, kn.astype(kc.dtype),
                                                     start),
                        jax.lax.dynamic_update_slice(vc, vn.astype(vc.dtype),
                                                     start))

            mesh = mesh_mod.get_mesh()
            mp_active = mesh is not None and mesh.shape.get("mp", 1) > 1
            in_place = off.ndim == 1 and s == 1 and not mp_active
            if in_place:
                from ..ops.pallas.kv_cache_append import (
                    kv_cache_append, whole_tiles)
                in_place = whole_tiles(*k_cache.shape[2:],
                                       k_cache._value.dtype)
            if in_place:
                # one new position a slot, each whole tiles of the cache: an
                # in-place row copy a slot, where `upd`'s vmapped write is a
                # scatter that XLA:TPU runs as a loop of B guarded updates.
                # The op's name says which write a layer took
                # (GraftProgram.op_counts; the engine's info() reads it).
                def append(kc, vc, kn, vn, off_):
                    bshd = ("dp", None, None, None)
                    return mesh_mod.shard_kernel(
                        kv_cache_append, [bshd] * 4 + [("dp",)], bshd)(
                            kc, vc, kn, vn, off_)

                kv_out = apply(append, k_cache, v_cache, k, v, off,
                               op_name="kv_cache_append")
            else:
                kv_out = apply(upd, k_cache, v_cache, k, v, off,
                               op_name="kv_cache_upd")
            k_cache, v_cache = kv_out[0], kv_out[1]
            s_max = k_cache.shape[1]

            q_dt = jnp.dtype(q._value.dtype).name
            ragged = s == 1 and not mp_active and q_dt in (
                "float32", "bfloat16")
            if ragged:
                from ..ops.pallas.decode_attention import (
                    ragged_decode_attention, reads_in_place)
                # a head that is not whole tiles of lanes (the `tiny`
                # shapes, a head of 64 or 96) keeps the masked attention
                # below: the kernel refuses a cache it would have to copy
                ragged = reads_in_place(k_cache.shape, v_cache.shape)
            if ragged:
                # single-token decode: ragged Pallas kernel walks only the
                # live prefix of the cache (O(t) per token, no [B,H,S_max]
                # probability tensor) — ops/pallas/decode_attention.py
                def rag(qq, kc, vc, off_):
                    # scalar offset -> uniform lengths; [B] offsets -> each
                    # slot attends exactly its own live prefix
                    lengths = jnp.broadcast_to(
                        jnp.asarray(off_ + 1, jnp.int32), (qq.shape[0],))
                    bshd = ("dp", None, None, None)
                    return mesh_mod.shard_kernel(
                        ragged_decode_attention,
                        [bshd, bshd, bshd, ("dp",)], bshd)(
                            qq, kc, vc, lengths)

                attn = apply(rag, q, k_cache, v_cache, off,
                             op_name="ragged_decode_attention")
            else:
                def mk_mask(_shape_ref, off_):
                    j = jnp.arange(s_max)[None, None, :]
                    i = jnp.arange(s)[None, :, None] + off_.reshape(-1, 1, 1)
                    allowed = j <= i                   # [1|B, S, S_max]
                    return jnp.where(allowed, 0.0, -1e30)[:, None]

                mask = apply(mk_mask, q, off, op_name="decode_mask")
                attn = F.scaled_dot_product_attention(q, k_cache, v_cache,
                                                      attn_mask=mask)
            attn = manip.reshape(attn, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(attn), (k_cache, v_cache)
        cp = self.config.context_parallel
        if cp:
            from ..parallel.context_parallel import sdpa_context_parallel
            attn = sdpa_context_parallel(q, k, v, mode=cp, is_causal=True)
        else:
            attn = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        attn = manip.reshape(attn, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(attn)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(h, m, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(m, h, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        self._seq_parallel = config.sequence_parallel

    def forward(self, x, position_offset=0, kv_cache=None):
        if self._seq_parallel:  # Megatron-SP resident: sequence over mp
            x = shard_constraint_t(x, *owned_spec(3, 1, "mp"))
        if kv_cache is not None:
            attn, new_cache = self.self_attn(self.input_layernorm(x),
                                             position_offset=position_offset,
                                             kv_cache=kv_cache)
            h = x + attn
            out = h + self.mlp(self.post_attention_layernorm(h))
            return out, new_cache
        h = x + self.self_attn(self.input_layernorm(x))
        out = h + self.mlp(self.post_attention_layernorm(h))
        if self._seq_parallel:
            out = shard_constraint_t(out, *owned_spec(3, 1, "mp"))
        return out


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, caches=None, position_offset=0):
        x = self.embed_tokens(input_ids)
        # context parallel: activations sequence-sharded over 'sep' model-wide
        seq_axis = "sep" if self.config.context_parallel else None
        x = shard_constraint_t(x, "dp", seq_axis, None)
        if caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, nc = layer(x, position_offset=position_offset,
                              kv_cache=cache)
                new_caches.append(nc)
            return self.norm(x), new_caches
        for i, layer in enumerate(self.layers):
            if self.config.recompute:
                from ..distributed.fleet.recompute import recompute
                x = recompute(layer, x)
            else:
                x = layer(x)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                            has_bias=False, gather_output=True)

    def forward(self, input_ids, caches=None, position_offset=0):
        if caches is not None:
            h, new_caches = self.llama(input_ids, caches=caches,
                                       position_offset=position_offset)
            return self.lm_head(h), new_caches
        h = self.llama(input_ids)
        return self.lm_head(h)

    def compute_loss(self, input_ids, labels):
        logits = self.forward(input_ids)
        loss = F.cross_entropy(logits, labels, reduction="mean")
        return loss

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """Per-layer (k, v) caches [B, S_max, H_kv, D] with static length:
        this family's whole per-slot state.  The serving engine takes
        whatever pytree a model returns here, every leaf with the slot axis
        first (`models/jamba.py` adds recurrent state beside K and V)."""
        cfg = self.config
        d = cfg.hidden_size // cfg.num_attention_heads
        dt = dtype or self.lm_head.weight.dtype
        shape = (batch_size, max_len, cfg.num_key_value_heads, d)
        return [(Tensor(jnp.zeros(shape, dt)), Tensor(jnp.zeros(shape, dt)))
                for _ in range(cfg.num_hidden_layers)]

    # the bodies of this family's compiled steps (`models/steps.py` holds
    # the contract and compiles them)
    step_name = "llama"

    def cached_step_body(self, tok, caches, off):
        """generate()'s step, serving both prefill ([B, P]) and decode
        ([B, 1]) at ONE scalar offset: the last row's logits."""
        logits, new_caches = self.forward(tok, caches=caches,
                                          position_offset=off)
        return (logits._value[:, -1, :],), new_caches

    def slot_step_body(self, tok, caches, off, last_pos,
                       return_logits=False):
        """Batch-slot serving step (inference/serving): like the cached
        generate step but with per-slot state — ``off`` is a [B] i32 vector
        (each slot decodes at its own position) and ``last_pos`` gathers the
        logits of each slot's last REAL token (bucketed prefill pads prompts
        on the right, so the interesting row is not always -1). Returns the
        GREEDY next token per slot ([B] i32 — argmax on device: shipping
        [B, vocab] logits to the host every step would serialize the decode
        loop on transfer; first-max tie-break matches np.argmax, so tokens
        are bitwise the generate() oracle's). One captured lowering per
        (batch, seq-bucket) aval signature.

        ``return_logits=True`` additionally returns each slot's last-token
        logits row ([B, vocab]) so the engine can run HOST-side per-slot
        temperature/top-p sampling; the greedy argmax still comes from the
        same on-device computation, so greedy rows in a mixed batch stay
        bitwise the argmax-only variant's."""
        logits, new_caches = self.forward(tok, caches=caches,
                                          position_offset=off)
        lv = logits._value
        last = lv[jnp.arange(lv.shape[0]), last_pos, :]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return ((nxt, last) if return_logits else (nxt,)), new_caches

    def verify_step_body(self, tok, caches, off):
        """Speculative-verify step (inference/serving/speculative): scores a
        whole [B, W] token WINDOW per slot in one call — row b holds the
        slot's pending token followed by W-1 draft proposals, ``off`` [B] is
        each slot's write cursor. The window rides the same per-slot offset
        plumbing the [B, 1] slot step uses: `_rope` broadcasts the [B]
        offset over the window positions, `kv_cache_upd` vmaps one
        dynamic_update_slice per row at its own cursor, and the decode mask
        lets window position i attend exactly positions <= off[b] + i — so
        position i sees precisely the prefix a sequential decode would have
        cached, and its argmax is bitwise the token the sequential path
        would emit (tests/test_serving.py asserts this end to end).

        Returns the per-position greedy argmax [B, W] i32 (the verify
        targets; one host transfer per verify, not per token). Rejected
        positions need no cache repair: the acceptance cursor just doesn't
        advance past them, later writes overwrite, and the ragged lengths
        keep them out of attention. One captured lowering per (B, W) aval
        signature — the engine always calls at [max_batch, k+1], so late
        joins reuse it."""
        logits, new_caches = self.forward(tok, caches=caches,
                                          position_offset=off)
        return (jnp.argmax(logits._value, axis=-1).astype(jnp.int32),), \
            new_caches

    @no_grad()
    def generate(self, input_ids, max_new_tokens=16, temperature=0.0,
                 use_cache=True, eos_token_id=None, pad_token_id=None):
        """Greedy / temperature sampling.

        use_cache=True (default) runs the compiled KV-cache decode: prefill
        once, then one O(S_max)-attention step per token (the reference's
        FusedMultiTransformer decode path). use_cache=False keeps the naive
        full-recompute loop (useful as a parity oracle).

        With ``eos_token_id``, each sequence stops at its first EOS (the EOS
        itself is kept): finished rows emit ``pad_token_id`` (default: the
        EOS id) deterministically from then on, and the loop halts early once
        EVERY row is finished — so the output length is
        ``prompt + min(max_new_tokens, tokens until all rows hit EOS)``."""
        ids = input_ids
        finished = None
        if eos_token_id is not None:
            finished = np.zeros(int(ids.shape[0]), dtype=bool)
            pad_id = eos_token_id if pad_token_id is None else pad_token_id

        def mask_eos(nxt):
            """Per-sequence finished mask: freeze rows that already emitted
            EOS to the pad token; returns (tokens_to_append, all_done)."""
            if finished is None:
                return nxt, False
            row = np.asarray(nxt.numpy()).reshape(-1)
            emitted = np.where(finished, pad_id, row)
            finished[:] = finished | (emitted == eos_token_id)
            return Tensor(jnp.asarray(emitted.reshape(-1, 1))), \
                bool(finished.all())
        if use_cache:
            b, p_len = ids.shape[0], ids.shape[1]
            s_max = p_len + max_new_tokens
            caches = [(kc._value, vc._value)
                      for kc, vc in self.init_kv_caches(b, s_max)]
            params = [p._value for p in self.parameters()]
            # one step a model: repeated generate() calls (and repeated
            # shapes within one) reuse its compiled programs
            step = compiled_step(self, "cached")
            last, caches = step(params, ids._value, caches,
                                jnp.asarray(0, jnp.int32))
            for t in range(max_new_tokens):
                nxt, done = mask_eos(self._sample(Tensor(last), temperature))
                ids = manip.concat([ids, nxt.astype(ids.dtype)], axis=1)
                if done or t == max_new_tokens - 1:
                    break
                last, caches = step(params, nxt._value, caches,
                                    jnp.asarray(p_len + t, jnp.int32))
            return ids
        for _ in range(max_new_tokens):
            logits = self.forward(ids)
            nxt, done = mask_eos(self._sample(logits[:, -1, :], temperature))
            ids = manip.concat([ids, nxt.astype(ids.dtype)], axis=1)
            if done:
                break
        return ids

    def _sample(self, last, temperature):
        if temperature and temperature > 0.0:
            probs = F.softmax(last / temperature, axis=-1)
            from ..ops.random import multinomial
            return multinomial(probs, 1)
        from ..ops.math import argmax
        return manip.unsqueeze(argmax(last, axis=-1), -1)


# ---------------------------------------------------------------------------
# Hybrid-parallel compiled train step (dp × pp × mp [+ sharding])
# ---------------------------------------------------------------------------

def _tree_of_params(layer):
    names, params = [], []
    for n, p in layer.named_parameters():
        names.append(n)
        params.append(p)
    return names, params


def _call_with_params(layer, names, vals, fn):
    params = [p for _, p in layer.named_parameters()]
    saved = [p._value for p in params]
    try:
        for p, v in zip(params, vals):
            p._value = v
        return fn()
    finally:
        for p, v in zip(params, saved):
            p._value = v


def _fused_head_ce(hv, wv, labels_val):
    """Mean CE of hidden states [B, S, H] through the lm head [H, V] by the
    fused Pallas kernel (ops/pallas/fused_ce.py), rows sharded over 'dp'."""
    from ..ops.pallas.fused_ce import fused_linear_cross_entropy
    flat = labels_val.reshape(-1)
    # F.cross_entropy semantics: ignore_index (-100) rows contribute
    # nothing and the mean divides by the VALID count only
    valid = flat != -100
    losses = mesh_mod.shard_kernel(
        fused_linear_cross_entropy,
        [("dp", None), (None, None), ("dp",)], ("dp",))(
            hv.reshape(-1, hv.shape[-1]), wv, jnp.where(valid, flat, 0))
    vf = valid.astype(losses.dtype)
    return jnp.sum(losses * vf) / jnp.maximum(jnp.sum(vf), 1.0)


def build_hybrid_train_step(model: LlamaForCausalLM, optimizer, mesh=None,
                            n_microbatches: int = 1, remat: bool = True,
                            amp: bool = False, schedule: str = "1f1b",
                            n_virtual: int = 1,
                            accumulate_steps: Optional[int] = None,
                            fused_loss: bool = False):
    """Build a fully-compiled hybrid train step.

    The decoder blocks' params are stacked on a leading dim of size L and
    - pp == 1: consumed via lax.scan over layers (fast compile),
    - pp  > 1: sharded over 'pp' (layers grouped into stages) and executed by
      the selected pipeline schedule, compiled into one XLA program:
      'gpipe' (fill-drain, AD backward), '1f1b' (manual fwd/bwd interleave,
      ring-buffer activation stash — pipeline_parallel.py:387 analog), or
      'vpp' (interleaved virtual stages, n_virtual chunks per pp rank —
      PipelineParallelWithInterleave:1016 analog).
    Embedding / final norm / lm head run outside the pipeline in GSPMD.

    accumulate_steps > 1 enables gradient merge (reference
    fleet/meta_optimizers/gradient_merge_optimizer.py semantics): the batch is
    split into that many micro-steps, grads accumulate across a lax.scan
    (one live grad buffer), and the optimizer applies the averaged grad once.
    Defaults to the optimizer's `_accumulate_steps` tag, set by
    fleet.distributed_optimizer from DistributedStrategy.gradient_merge /
    pipeline_configs["accumulate_steps"].
    Returns step(batch_dict) -> loss Tensor.
    """
    mesh = mesh if mesh is not None else mesh_mod.get_mesh()
    cfg = model.config
    L = cfg.num_hidden_layers
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    if schedule == "1f1b_fused":  # alias used by activation accounting
        schedule = "1f1b"
    if schedule not in ("gpipe", "1f1b", "1f1b_compact", "vpp"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(gpipe | 1f1b | 1f1b_compact | vpp)")
    if pp <= 1:
        schedule = "gpipe"
    if schedule == "vpp":
        assert L % (pp * n_virtual) == 0, "layers must divide pp*n_virtual"
        assert n_microbatches % pp == 0, "vpp needs n_microbatches % pp == 0"
    else:
        n_virtual = 1
    assert L % max(pp, 1) == 0, "layers must divide pp degree"

    # fused lm-head+CE (Pallas, ops/pallas/fused_ce.py): skips the [B,S,V]
    # logits materialization and its cotangent.  The mp>1 vocab-sharded head
    # runs in GSPMD auto mode where a pallas_call would force a W gather, so
    # the fusion is gated to mp==1 (the TP variant lives in
    # fused_linear_cross_entropy_tp for shard_map callers).
    use_fused_loss = fused_loss and (
        mesh is None or mesh.shape.get("mp", 1) <= 1)

    def _head_ce(h_val, labels_val):
        """norm -> lm head -> CE for the full [B,S,H] h_val (model params
        already installed by the caller's outer_apply)."""
        h_out = model.llama.norm(Tensor(h_val))
        if use_fused_loss:
            return _fused_head_ce(h_out._value, model.lm_head.weight._value,
                                  labels_val)
        logits = model.lm_head(h_out)
        if amp:  # softmax/CE in fp32 for numeric stability
            logits = Tensor(logits._value.astype(jnp.float32))
        return F.cross_entropy(logits, Tensor(labels_val),
                               reduction="mean")._value

    block0 = model.llama.layers[0]
    block_names, _ = _tree_of_params(block0)

    # stack per-layer params: dict name -> [L, ...]
    stacked = {}
    for n in block_names:
        vals = []
        for li in range(L):
            blk = model.llama.layers[li]
            vals.append(dict(blk.named_parameters())[n]._value)
        stacked[n] = jnp.stack(vals, 0)
    if schedule == "vpp":
        # Store chunk-major [v, pp, L/(pp*v), ...] AT REST (element [c, i] =
        # virtual stage c*pp+i's layer block; flat C-order position equals
        # layer index, so reshape is exactly the cyclic layout). Sharding
        # dim 1 over pp then matches the schedule's view — no per-step
        # parameter redistribution.
        stacked = {n: a.reshape(n_virtual, pp, -1, *a.shape[1:])
                   for n, a in stacked.items()}

    # non-block params
    outer_names, outer_params = [], []
    for n, p in model.named_parameters():
        if ".layers." in n:
            continue
        outer_names.append(n)
        outer_params.append(p)

    def block_apply(pvals_dict, x):
        """Pure: run one decoder block with given param values."""
        vals = [pvals_dict[n] for n in block_names]
        return _call_with_params(
            block0, block_names, vals,
            lambda: block0(Tensor(x))._value)

    def blocks_scan(stacked_vals, x):
        def body(carry, layer_params):
            return block_apply(layer_params, carry), None
        fn = jax.checkpoint(body) if remat else body
        out, _ = jax.lax.scan(fn, x, stacked_vals)
        return out

    def stage_fn(stage_params, x):
        # stage_params: dict name -> [L/pp, ...]
        return blocks_scan(stage_params, x)

    def outer_apply(outer_vals, fn):
        saved = [p._value for p in outer_params]
        try:
            for p, v in zip(outer_params, outer_vals):
                p._value = v
            return fn()
        finally:
            for p, v in zip(outer_params, saved):
                p._value = v

    def _amp_cast(tree):
        """bf16 compute with fp32 master params: the cast is differentiable,
        so grads flow back to (and optimizer states stay in) fp32."""
        return jax.tree_util.tree_map(
            lambda v: v.astype(jnp.bfloat16)
            if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)

    def microbatched(v):
        """[B, ...] -> [n_microbatches, B / n_microbatches, ...] with each
        microbatch's rows over 'dp': a bare reshape would hand each dp
        replica whole microbatches instead."""
        v = v.reshape(n_microbatches, v.shape[0] // n_microbatches,
                      *v.shape[1:])
        return mesh_mod.shard_constraint(v, None, "dp",
                                         *([None] * (v.ndim - 2)))

    def loss_fn(params, batch, rng):
        outer_vals, stacked_vals = params
        if amp:
            outer_vals = _amp_cast(outer_vals)
            stacked_vals = _amp_cast(stacked_vals)
        ids, labels = batch["input_ids"], batch["labels"]

        with gen.key_override(rng), no_grad():
            def run():
                x = model.llama.embed_tokens(Tensor(ids))._value
                if amp:
                    x = x.astype(jnp.bfloat16)
                x = mesh_mod.shard_constraint(x, "dp", None, None)
                if pp > 1:
                    b, s, h = x.shape
                    assert b % n_microbatches == 0
                    x_mb = microbatched(x)
                    y_mb = spmd_pipeline(
                        stage_fn, stacked_vals, x_mb,
                        n_microbatches=n_microbatches,
                        mesh=mesh, remat=remat,
                        schedule="vpp" if schedule == "vpp" else "gpipe",
                        n_virtual=n_virtual)
                    x2 = y_mb.reshape(b, s, h)
                else:
                    x2 = blocks_scan(stacked_vals, x)
                return _head_ce(x2, labels)
            return outer_apply(outer_vals, run)

    # --- 1F1B: loss AND grads from the manually-scheduled pipeline ---------
    # (value_and_grad cannot interleave fwd/bwd microbatches; the schedule
    # computes its own vjps, so the embedding/head grads are chained on
    # manually around spmd_pipeline_1f1b.)
    embed_pos = [i for i, n in enumerate(outer_names) if "embed_tokens" in n]
    head_pos = [i for i, n in enumerate(outer_names) if "embed_tokens" not in n]

    def loss_and_grads_1f1b(params, batch, rng):
        from ..parallel.pipeline import spmd_pipeline_1f1b
        f1b_variant = "compact" if schedule == "1f1b_compact" else "fused"

        outer_vals, stacked_vals = params
        cast_outer = _amp_cast(outer_vals) if amp else list(outer_vals)
        cast_stacked = _amp_cast(stacked_vals) if amp else stacked_vals
        ids, labels = batch["input_ids"], batch["labels"]
        assert ids.shape[0] % n_microbatches == 0

        with gen.key_override(rng), no_grad():
            def embed_fn(embed_vals):
                full = list(cast_outer)
                for k, i in enumerate(embed_pos):
                    full[i] = embed_vals[k]

                def run():
                    x = model.llama.embed_tokens(Tensor(ids))._value
                    if amp:
                        x = x.astype(jnp.bfloat16)
                    x = mesh_mod.shard_constraint(x, "dp", None, None)
                    return microbatched(x)
                return outer_apply(full, run)

            x_mb, embed_vjp = jax.vjp(
                embed_fn, [cast_outer[i] for i in embed_pos])

            def head_loss(head_vals, y, labels_mb):
                full = list(cast_outer)
                for k, i in enumerate(head_pos):
                    full[i] = head_vals[k]

                def run():
                    return _head_ce(y, labels_mb)
                return outer_apply(full, run)

            labels_mb = microbatched(labels)
            loss, g_stacked, g_head, dx_mb = spmd_pipeline_1f1b(
                stage_fn, head_loss, cast_stacked,
                [cast_outer[i] for i in head_pos], x_mb, labels_mb,
                n_microbatches=n_microbatches, mesh=mesh, remat=remat,
                variant=f1b_variant)
            (g_embed,) = embed_vjp(dx_mb)

        # assemble grads positionally, cast back to master-param dtype
        outer_grads = [None] * len(outer_names)
        for k, i in enumerate(embed_pos):
            outer_grads[i] = g_embed[k].astype(outer_vals[i].dtype)
        for k, i in enumerate(head_pos):
            outer_grads[i] = g_head[k].astype(outer_vals[i].dtype)
        g_stacked = {k: g.astype(stacked_vals[k].dtype)
                     for k, g in g_stacked.items()}
        return loss, (outer_grads, g_stacked)

    # shardings
    def stacked_spec(name, arr):
        # leading layer dim(s) over pp; inner dims follow the layer's TP spec
        p = dict(block0.named_parameters())[name]
        n_lead = 3 if schedule == "vpp" else 1
        inner = _clean_spec(getattr(p, "_sharding", None), arr.ndim - n_lead,
                            mesh)
        lead = "pp" if (mesh is not None and mesh.shape.get("pp", 1) > 1) else None
        if mesh is None:
            return None
        if schedule == "vpp":
            return PartitionSpec(None, lead, None, *inner)
        return PartitionSpec(lead, *inner)

    from jax.sharding import NamedSharding, PartitionSpec

    def _clean_spec(spec, ndim, mesh):
        out = []
        spec = spec or ()
        for i in range(ndim):
            s = spec[i] if i < len(spec) else None
            if s is not None and mesh is not None and s in mesh.axis_names \
                    and mesh.shape[s] > 1:
                out.append(s)
            else:
                out.append(None)
        return out

    if mesh is not None:
        outer_sh = [NamedSharding(mesh, PartitionSpec(
            *_clean_spec(getattr(p, "_sharding", None), p._value.ndim, mesh)))
            for p in outer_params]
        stacked_sh = {n: NamedSharding(mesh, stacked_spec(n, a))
                      for n, a in stacked.items()}
        outer_vals = [jax.device_put(p._value, s)
                      for p, s in zip(outer_params, outer_sh)]
        stacked = {n: jax.device_put(a, stacked_sh[n])
                   for n, a in stacked.items()}
    else:
        outer_sh, stacked_sh = None, None
        outer_vals = [p._value for p in outer_params]

    params = (outer_vals, stacked)

    base_opt = optimizer
    while hasattr(base_opt, "inner_opt"):
        base_opt = base_opt.inner_opt
    if accumulate_steps is None:
        accumulate_steps = int(getattr(base_opt, "_accumulate_steps", 1) or 1)
    _, opt_update = base_opt.functional_update()

    def init_state(tree):
        return jax.tree_util.tree_map(
            lambda v: base_opt._init_state(Parameter(v)), tree,
            is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
    opt_state = init_state(params)

    # ZeRO: shard optimizer-state leaves over the sharding axis (stage >= 1);
    # with no 'sharding' mesh axis the shard rides dp (Fleet default
    # sharding degree == dp degree — see _resolve_zero_axis)
    from ..parallel.trainer import _resolve_zero_axis
    zero_axis = _resolve_zero_axis(getattr(base_opt, "_shard_axis", None), mesh)
    zero_stage = getattr(base_opt, "_shard_stage", 0)
    if mesh is not None and zero_axis and zero_stage >= 1 \
            and mesh.shape.get(zero_axis, 1) > 1:
        from ..parallel.trainer import _zero_state_spec

        def shard_states(state_tree, sharding_tree):
            flat_s, sdef = jax.tree_util.tree_flatten(
                state_tree, is_leaf=lambda x: isinstance(x, dict)
                and all(hasattr(v, "shape") for v in x.values()))
            flat_sh = jax.tree_util.tree_flatten(
                sharding_tree, is_leaf=lambda x: isinstance(x, NamedSharding))[0]
            out = []
            for st, psh in zip(flat_s, flat_sh):
                new = {}
                for k, v in st.items():
                    spec = _zero_state_spec(psh.spec, v.shape, zero_axis, mesh)
                    new[k] = jax.device_put(v, NamedSharding(mesh, spec))
                out.append(new)
            return sdef.unflatten(out)

        opt_state = (shard_states(opt_state[0], outer_sh),
                     shard_states(opt_state[1], stacked_sh))

    def loss_and_grads(param_vals, batch, rng):
        if schedule in ("1f1b", "1f1b_compact") and pp > 1:
            return loss_and_grads_1f1b(param_vals, batch, rng)
        return jax.value_and_grad(loss_fn)(param_vals, batch, rng)

    def pure_step(param_vals, opt_st, batch, lr, step, rng):
        if accumulate_steps > 1:
            k = accumulate_steps
            micro = jax.tree_util.tree_map(
                lambda v: v.reshape(k, v.shape[0] // k, *v.shape[1:]), batch)
            if mesh is not None and mesh.shape.get("dp", 1) > 1:
                micro = jax.tree_util.tree_map(
                    lambda v: jax.lax.with_sharding_constraint(
                        v, NamedSharding(mesh, PartitionSpec(
                            None, "dp", *([None] * (v.ndim - 2))))), micro)

            def body(acc, inp):
                mb, i = inp
                l, g = loss_and_grads(param_vals, mb,
                                      jax.random.fold_in(rng, i))
                acc_l, acc_g = acc
                new_g = jax.tree_util.tree_map(lambda a, b: a + b, acc_g, g)
                return (acc_l + l, new_g), None

            zero_g = jax.tree_util.tree_map(jnp.zeros_like, param_vals)
            (tot_l, tot_g), _ = jax.lax.scan(
                body, (jnp.asarray(0.0, jnp.float32), zero_g),
                (micro, jnp.arange(k)))
            loss = tot_l / k
            grads = jax.tree_util.tree_map(lambda g: g / k, tot_g)
        else:
            loss, grads = loss_and_grads(param_vals, batch, rng)
        # comms hook (distributed/comms): with comms.quantized() active at
        # trace time the dp gradient sync re-rides the quantized wire;
        # off = identity, bitwise (same contract as parallel/trainer.py)
        from ..distributed import comms as _comms
        grads = _comms.grad_sync(grads, mesh=mesh, axis="dp")
        clip = getattr(base_opt, "_grad_clip", None)
        if clip is not None:
            from ..nn.clip import ClipGradByGlobalNorm
            if isinstance(clip, ClipGradByGlobalNorm):
                leaves = jax.tree_util.tree_leaves(grads)
                gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                  for g in leaves))
                scale = clip.clip_norm / jnp.maximum(gn, clip.clip_norm)
                grads = jax.tree_util.tree_map(
                    lambda g: g * scale.astype(g.dtype), grads)
        flat_p, tdef = jax.tree_util.tree_flatten(param_vals)
        flat_g = jax.tree_util.tree_flatten(grads)[0]
        flat_s = tdef.flatten_up_to(opt_st)
        outs = []
        for v, g, s in zip(flat_p, flat_g, flat_s):
            s = dict(s)
            s["__step__"] = step
            wd = base_opt._weight_decay
            nv, ns = base_opt._update_rule(
                v, g.astype(v.dtype), s, lr,
                0.0 if wd is None or callable(wd) else wd)
            ns.pop("__step__", None)
            outs.append((nv, ns))
        new_p = tdef.unflatten([o[0] for o in outs])
        new_s = tdef.unflatten([o[1] for o in outs])
        return loss, new_p, new_s

    jitted = jax.jit(pure_step, donate_argnums=(0, 1))

    state = {"params": params, "opt": opt_state, "step": 0}

    def _args(batch, step_no):
        """The jitted step's arguments for one batch, placed as it runs
        them: the batch sharded over 'dp' when the mesh has one."""
        vals = {k: (v._value if isinstance(v, Tensor) else jnp.asarray(v))
                for k, v in batch.items()}
        if mesh is not None and mesh.shape.get("dp", 1) > 1:
            dp_sh = NamedSharding(mesh, PartitionSpec("dp"))
            vals = {k: jax.device_put(v, dp_sh) for k, v in vals.items()}
        return (state["params"], state["opt"], vals,
                jnp.asarray(base_opt.get_lr(), jnp.float32),
                jnp.asarray(step_no, jnp.int32), gen.next_key())

    def step(batch):
        state["step"] += 1
        loss, state["params"], state["opt"] = jitted(
            *_args(batch, state["step"]))
        return Tensor(loss)

    def lower_text(batch):
        """StableHLO of the EXACT compiled train step (for kernel-provenance
        checks: ops/pallas/_common.kernel_names finds the Pallas kernels)."""
        return jitted.lower(*_args(batch, 1)).as_text()

    def compiled(batch):
        """The EXACT train step compiled for the mesh: its ``as_text()`` is
        the partitioned HLO one device runs, with that device's shapes."""
        return jitted.lower(*_args(batch, 1)).compile()

    def memory_stats(batch):
        """Per-device CompiledMemoryStats of the EXACT compiled train step
        (argument/output/temp/peak bytes from XLA buffer assignment) — the
        instrument behind the compiled-ZeRO memory-scaling guarantee
        (tests/test_zero_memory.py; reference group_sharded_stage3.py:59
        claims the same 1/shard-degree scaling for its GPU sharding)."""
        return compiled(batch).memory_analysis()

    def analyze_comm(batch):
        """Comm-volume + overlap-slot columns of the EXACT step program
        (jit/passes/comm_schedule.analyze): collective count, payload
        bytes, slots — what the multichip dryrun prints."""
        from ..jit.passes import comm_schedule as _cs
        return _cs.analyze(jax.make_jaxpr(pure_step)(*_args(batch, 1)))

    step.state = state
    step.lower_text = lower_text
    step.compiled = compiled
    step.memory_stats = memory_stats
    step.analyze_comm = analyze_comm
    step.write_back = lambda: _write_back(model, state["params"], outer_names,
                                          outer_params, block_names)
    return step


def _write_back(model, params, outer_names, outer_params, block_names):
    """Copy trained values back into the model's Parameters (real copies:
    the step's own buffers get donated on the next call)."""
    outer_vals, stacked = params
    for p, v in zip(outer_params, outer_vals):
        p._value = jnp.copy(v)
    L = model.config.num_hidden_layers
    for n in block_names:
        # vpp stores chunk-major [v, pp, Lb, ...]; flat C-order == layer order
        pshape = dict(model.llama.layers[0].named_parameters())[n]._value.shape
        layer_vals = jnp.copy(stacked[n]).reshape(L, *pshape)
        for li in range(L):
            dict(model.llama.layers[li].named_parameters())[n]._value = layer_vals[li]
