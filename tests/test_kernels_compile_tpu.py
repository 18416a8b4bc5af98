"""Sandbox pre-check: AOT-compile the Pallas kernels for the TPU v5e.

libtpu ships a compile-only client (`jax.experimental.topologies`), so the
Mosaic compiler can be asked whether it accepts a kernel without a chip:
arguments are `ShapeDtypeStruct`s placed on the topology's devices and the
program is lowered and compiled, never run.  Shapes are the ones
`chip_smoke.py` runs (Llama-7B layer geometry).  Numerics are not checked
here — the interpret-mode parity tests (test_pallas_fused_kernels.py,
test_flash_attention.py) and the smoke's kernels phase on the chip do that.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import paddle_tpu as P
from paddle_tpu.autograd.grad_mode import no_grad
from paddle_tpu.core import device as core_device
from paddle_tpu.jit import capture
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama
from paddle_tpu.models.steps import build_step
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas._common import kernel_names
from paddle_tpu.ops.pallas.decode_attention import (
    mqa_decode_attention, ragged_decode_attention)
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy
from paddle_tpu.ops.pallas.kv_cache_append import kv_cache_append
from paddle_tpu.ops.pallas.selective_scan import (
    selective_scan, ssm_decode_update)
from paddle_tpu.parallel import mesh as mesh_mod

B, S, H, D, HID, VOCAB = 2, 2048, 32, 128, 4096, 32000   # chip_smoke.py's


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except (RuntimeError, ValueError, NotImplementedError) as e:
        pytest.skip("libtpu offers no compile-only v5e topology here: "
                    f"{type(e).__name__}: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(autouse=True)
def _target_tpu(monkeypatch):
    # the kernels pick Mosaic vs interpret mode from the default backend,
    # which in the sandbox is the CPU
    monkeypatch.setattr(core_device, "is_tpu_backend", lambda: True)
    yield
    mesh_mod.set_mesh(None)


def _lower(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()            # raises if Mosaic / XLA:TPU refuse it
    return kernel_names(lowered.as_text())


def _on(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def test_flash_attention_fwd_bwd(v5e):
    a = _on(SingleDeviceSharding(v5e[0]))((B, S, H, D), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None).astype(jnp.float32).sum()

    assert _lower(jax.grad(loss, argnums=(0, 1, 2)), a, a, a) == [
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv"]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("heads,kv_heads,head_dim",
                         [(32, 32, 128), (32, 8, 128), (16, 16, 64)])
def test_ragged_decode_attention(v5e, dtype, heads, kv_heads, head_dim):
    sds = _on(SingleDeviceSharding(v5e[0]))
    args = (sds((8, 1, heads, head_dim), dtype),
            sds((8, S, kv_heads, head_dim), dtype),
            sds((8, S, kv_heads, head_dim), dtype), sds((8,), jnp.int32))
    if head_dim % 128:
        # since PR 32 a cache of 64 lanes is not padded (copied) every call:
        # it is refused, typed, and the message names the allocation to make
        from paddle_tpu.ops.pallas.decode_attention import (
            CacheLayoutUnsupported)
        with pytest.raises(CacheLayoutUnsupported, match="128 lanes"):
            jax.jit(ragged_decode_attention).lower(*args)
        return
    assert _lower(ragged_decode_attention, *args) == [
        "ragged_decode_attention"]


def _copies_of(compiled_text, *shapes):
    """The `copy` instructions of a compiled program whose result is one of
    `shapes` (as HLO prints them, "64,1536,8,128")."""
    return [line.strip() for line in compiled_text.splitlines()
            if re.search(r"\[(%s)\]\S* copy\(" % "|".join(shapes), line)]


@pytest.mark.parametrize("slots,positions,heads", [
    (64, 1536, 16),             # internlm2-serve-decode
    (24, 3072, 32),             # mistral7b-serve-chat
])
def test_ragged_decode_attention_at_the_serving_cells(v5e, slots, positions,
                                                      heads):
    """Both cells' geometry (8 KV heads of 128, bf16), and the cache reaches
    the kernel as (position, KV head) rows without being copied."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    cache = sds((slots, positions, 8, 128), jnp.bfloat16)
    lowered = jax.jit(ragged_decode_attention).lower(
        sds((slots, 1, heads, 128), jnp.bfloat16), cache, cache,
        sds((slots,), jnp.int32))
    assert kernel_names(lowered.as_text()) == ["ragged_decode_attention"]
    assert not _copies_of(lowered.compile().as_text(),
                          f"{slots},{positions},8,128",
                          f"{slots},{positions * 8},128")


def _equations(jaxpr):
    """Equations of a jaxpr and of every jaxpr its equations hold."""
    return sum(1 + sum(map(_equations, jax.core.jaxprs_in_params(e.params)))
               for e in jaxpr.eqns)


def _kernel_bodies(jaxpr):
    """The body of every `pallas_call` a jaxpr holds, at any depth."""
    return [body for e in jaxpr.eqns for body in (
        [e.params["jaxpr"]] if e.primitive.name == "pallas_call" else
        sum(map(_kernel_bodies, jax.core.jaxprs_in_params(e.params)), []))]


def test_ragged_decode_attention_body_does_not_grow_with_heads(v5e):
    """A body that loops over heads or group members in Python is traced and
    lowered once a layer in every process, before any compile cache is asked:
    the kernel's jaxpr is as long at 32 query heads as at 8."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    cache = sds((8, S, 8, 128), jnp.bfloat16)
    sizes = set()
    for heads in (8, 16, 32):
        jaxpr = jax.make_jaxpr(ragged_decode_attention)(
            sds((8, 1, heads, 128), jnp.bfloat16), cache, cache,
            sds((8,), jnp.int32))
        body, = _kernel_bodies(jaxpr.jaxpr)
        sizes.add(_equations(body))
    assert len(sizes) == 1, sizes


def test_fused_ce_fwd_bwd_at_7b_head(v5e):
    sds = _on(SingleDeviceSharding(v5e[0]))

    def loss(h, w, lab):
        return fused_linear_cross_entropy(h, w, lab).sum()

    names = _lower(jax.grad(loss, argnums=(0, 1)),
                   sds((B * S, HID), jnp.bfloat16),
                   sds((HID, VOCAB), jnp.bfloat16),
                   sds((B * S,), jnp.int32))
    assert names == ["fused_ce_fwd", "fused_ce_bwd_dh", "fused_ce_bwd_dw"]


def test_attention_under_dp2_mp2(v5e):
    """What the hybrid step produces: batch on 'dp', heads on 'mp'.  A bare
    pallas_call fed such operands is refused ("Mosaic kernels cannot be
    automatically partitioned"); sdpa wraps it in a shard_map."""
    mesh = mesh_mod.init_mesh({"dp": 2, "mp": 2}, devices=v5e)
    a = _on(NamedSharding(mesh, PartitionSpec("dp", None, "mp", None)))(
        (4, S, H, D), jnp.bfloat16)

    def loss(q, k, v):
        with no_grad():
            out = F.scaled_dot_product_attention(
                P.Tensor(q), P.Tensor(k), P.Tensor(v), is_causal=True)
        return out._value.astype(jnp.float32).sum()

    assert len(_lower(jax.grad(loss, argnums=(0, 1, 2)), a, a, a)) == 3


def test_fused_head_ce_under_dp2(v5e):
    mesh = mesh_mod.init_mesh({"dp": 2, "mp": 1}, devices=v5e[:2])
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    rep = NamedSharding(mesh, PartitionSpec())
    names = _lower(jax.grad(llama._fused_head_ce, argnums=(0, 1)),
                   _on(rows)((4, S, HID), jnp.bfloat16),
                   _on(rep)((HID, VOCAB), jnp.bfloat16),
                   _on(rows)((4, S), jnp.int32))
    assert names == ["fused_ce_fwd", "fused_ce_bwd_dh", "fused_ce_bwd_dw"]


@pytest.mark.parametrize("dtype,kernels", [
    ("bfloat16", ["ragged_decode_attention"]),
    ("float16", []),      # Mosaic has no f16 vectors: llama routes it to sdpa
])
def test_llama_slot_step_decode(v5e, dtype, kernels):
    """The serving decode step ([B, 1] tokens over a cache in the weights'
    dtype) as the engine builds it, one layer at a 128-wide head."""
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab=512, hidden=256, layers=1, heads=2, inter=512, seq=S))
    {"bfloat16": model.bfloat16, "float16": model.half}[dtype]()
    sds = _on(SingleDeviceSharding(v5e[0]))

    def like(x):
        return sds(x.shape, x.dtype)

    params = [like(p._value) for p in model.parameters()]
    caches = [(like(k._value), like(v._value))
              for k, v in model.init_kv_caches(8, S)]
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds((8, 1), jnp.int32), caches,
                         sds((8,), jnp.int32), sds((8,), jnp.int32))
    lowered.compile()
    assert kernel_names(lowered.as_text()) == kernels


@pytest.mark.parametrize("slots,positions,dp", [
    (64, 1536, 1), (24, 3072, 1), (64, 1536, 2)],
    ids=["internlm2-serve-decode", "mistral7b-serve-chat", "slots-over-dp2"])
def test_kv_cache_append_in_place_at_the_cells_shapes(v5e, slots, positions,
                                                      dp):
    """The decode step's K/V write of the two Llama-family serving cells,
    caches donated: one custom call, no loop, no copy of a cache; the same
    with the slots sharded over 'dp', as `llama.py` wraps it."""
    if dp > 1:
        mesh = mesh_mod.init_mesh({"dp": dp, "mp": 1}, devices=v5e[:dp])
        sds = _on(NamedSharding(mesh, PartitionSpec("dp")))
    else:
        sds = _on(SingleDeviceSharding(v5e[0]))
    cache = sds((slots, positions, 8, 128), jnp.bfloat16)
    row = sds((slots, 1, 8, 128), jnp.bfloat16)
    bshd = ("dp", None, None, None)
    write = mesh_mod.shard_kernel(kv_cache_append, [bshd] * 4 + [("dp",)],
                                  bshd)
    lowered = jax.jit(write, donate_argnums=(0, 1)).lower(
        cache, cache, row, row, sds((slots,), jnp.int32))
    assert kernel_names(lowered.as_text()) == ["kv_cache_append"]
    text = lowered.compile().as_text()
    assert "kv_cache_append" in text and not re.search(r" while\(", text)
    assert not re.search(
        rf"bf16\[{slots // dp},{positions},8,128\]\S* copy", text)


def test_llama_slot_step_at_internlm2_widths_has_no_scatter_loop(v5e):
    """The slot step of `internlm2-serve-decode` (64 slots x 1536, 16 heads,
    8 KV heads of 128; two layers, MLP and vocabulary cut: neither is in
    question): XLA:TPU expands a scatter into a `while` of one turn a slot,
    so a step that holds neither writes every layer's rows in place."""
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=2048, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1536))
    model.bfloat16()
    sds = _on(SingleDeviceSharding(v5e[0]))
    params = [sds(p.shape, p._value.dtype) for p in model.parameters()]
    cache = sds((64, 1536, 8, 128), jnp.bfloat16)
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds((64, 1), jnp.int32), [(cache, cache)] * 2,
                         sds((64,), jnp.int32), sds((64,), jnp.int32))
    # the decode kernel is one jitted function that both layers call
    assert kernel_names(lowered.as_text()) == [
        "kv_cache_append", "kv_cache_append", "ragged_decode_attention"]
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not re.search(r" (while|scatter)\(", text)
    assert not _copies_of(text, "64,1536,8,128", "64,12288,128")


def test_llama_slot_step_at_a_head_of_64_lanes_keeps_the_masked_attention(
        v5e):
    """The decode kernel refuses a cache of 64 lanes (it used to pad and copy
    it every step), so the family's decode step takes its masked attention
    there and still compiles for the chip: no decode kernel in the step."""
    P.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=512, hidden_size=512, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
        max_position_embeddings=S))
    model.bfloat16()
    sds = _on(SingleDeviceSharding(v5e[0]))
    params = [sds(p.shape, p._value.dtype) for p in model.parameters()]
    cache = sds((8, S, 8, 64), jnp.bfloat16)
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds((8, 1), jnp.int32), [(cache, cache)] * 2,
                         sds((8,), jnp.int32), sds((8,), jnp.int32))
    lowered.compile()
    assert kernel_names(lowered.as_text()) == []


def test_one_kv_head_as_rows_and_folded(v5e):
    """At H_kv = 1 a [chunk, 1, D] slab is not whole tiles and Mosaic refuses
    to slice it; as (position, KV head) rows the 4-D cache compiles, and so
    does the cache with the head axis folded away (the layout
    models/jamba.py keeps for its attention layers)."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    lens = sds((8,), jnp.int32)
    for dtype in (jnp.bfloat16, jnp.float32):
        q, kv = sds((8, 1, 20, 128), dtype), sds((8, S, 128), dtype)
        kv4 = sds((8, S, 1, 128), dtype)
        assert _lower(ragged_decode_attention, q, kv4, kv4, lens) == [
            "ragged_decode_attention"]
        assert _lower(mqa_decode_attention, q, kv, kv, lens) == [
            "mqa_decode_attention"]


@pytest.mark.parametrize("seq", [128, 512])
def test_selective_scan_at_published_widths(v5e, seq):
    """d_inner 5120, d_state 16, a prefill bucket of positions, batch 1."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    act = sds((1, seq, 5120), jnp.bfloat16)
    bc = sds((1, seq, 16), jnp.bfloat16)
    assert _lower(selective_scan, act, sds((1, seq, 5120), jnp.float32),
                  sds((5120, 16), jnp.float32), bc, bc,
                  sds((5120,), jnp.bfloat16), act,
                  sds((1, 16, 5120), jnp.float32),
                  sds((1,), jnp.int32)) == ["selective_scan"]


@pytest.mark.parametrize("tokens,kernels", [
    # decode: ONE jitted body of the state's update, 3 callers
    ((8, 1), ["mqa_decode_attention", "ssm_decode_update"]),
    ((1, 128), ["selective_scan"]),      # prefill: ONE jitted body, 3 callers
], ids=["decode", "prefill"])
def test_jamba_slot_step(v5e, tokens, kernels):
    """The hybrid model's serving step as the engine builds it: one period
    (3 Mamba layers, 1 attention layer at a 128-wide head, one KV head) over
    a state of two kinds."""
    from paddle_tpu.models import JambaConfig, JambaForCausalLM
    P.seed(0)
    model = JambaForCausalLM(JambaConfig.tiny(
        vocab=512, hidden=256, layers=4, heads=2, inter=512, seq=S))
    model.bfloat16()
    sds = _on(SingleDeviceSharding(v5e[0]))
    like = lambda x: sds(x.shape, x.dtype)
    params = [like(p._value) for p in model.parameters()]
    b = tokens[0]
    caches = [(like(x._value), like(y._value))
              for x, y in model.init_kv_caches(b, S)]
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds(tokens, jnp.int32), caches,
                         sds((b,), jnp.int32), sds((b,), jnp.int32))
    lowered.compile()
    assert kernel_names(lowered.as_text()) == kernels


def _ssm_decode_args(sds, slots, n_state=16, di=5120):
    row, f32 = sds((slots, di), jnp.bfloat16), jnp.float32
    return (row, sds((slots, di), f32), sds((di, n_state), f32),
            sds((slots, n_state), f32), sds((slots, n_state), f32),
            sds((di,), jnp.bfloat16), row, sds((slots, n_state, di), f32))


@pytest.mark.parametrize("slots", [192, 64],
                         ids=["jamba2-3b-serve-reasoning",
                              "phi4-flash-serve-longctx"])
def test_ssm_decode_update_in_place_at_the_cells_shapes(v5e, slots):
    """The Mamba decode update at d_inner 5120, d_state 16, over both
    cells' slots, the state donated: one custom call whose state output IS
    its input (no copy of the state), and a body of the same length at
    either batch (a body that grew with the slots would be traced and
    lowered again in every process's set-up)."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    args = _ssm_decode_args(sds, slots)
    lowered = jax.jit(ssm_decode_update, donate_argnums=(7,)).lower(*args)
    assert kernel_names(lowered.as_text()) == ["ssm_decode_update"]
    compiled = lowered.compile()
    assert not _copies_of(compiled.as_text(), f"{slots},16,5120")
    assert compiled.memory_analysis().alias_size_in_bytes == \
        slots * 16 * 5120 * 4
    sizes = {_equations(body) for b in (64, 192) for body in _kernel_bodies(
        jax.make_jaxpr(ssm_decode_update)(*_ssm_decode_args(sds, b)).jaxpr)}
    assert len(sizes) == 1, sizes


def test_jamba_slot_step_writes_the_state_in_place(v5e):
    """A Mamba layer at the published d_inner 5120 and d_state 16 over
    the cell's 192 slots, as the engine builds the decode step (MLP and
    vocabulary cut: neither is in question).  The jnp update
    (`ssm_decode_ref`) compiles to a fusion that stages each new state in
    VMEM: a `copy-start` / `copy-done` of the whole [192, 16, 5120] float32
    state in and another back to HBM; the kernel reads the donated state in
    place and the step holds no copy of it."""
    from paddle_tpu.models import JambaConfig, JambaForCausalLM
    P.seed(0)
    model = JambaForCausalLM(JambaConfig.tiny(
        vocab=512, hidden=2560, layers=1, heads=20, inter=512, d_state=16,
        dt_rank=160, seq=256))
    model.bfloat16()
    sds = _on(SingleDeviceSharding(v5e[0]))
    like = lambda x: sds(x.shape, x.dtype)
    params = [like(p._value) for p in model.parameters()]
    caches = [(like(x._value), like(y._value))
              for x, y in model.init_kv_caches(192, 256)]
    assert caches[0][1].shape == (192, 16, 5120)
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds((192, 1), jnp.int32), caches,
                         sds((192,), jnp.int32), sds((192,), jnp.int32))
    assert kernel_names(lowered.as_text()) == ["ssm_decode_update"]
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(r"f32\[192,16,5120\]\S* copy(-start|-done)?\(",
                         text)


# -- MiMo-V2-Flash: routed experts, window and full attention (PR 32) ---------

MIMO = dict(slots=128, positions=8192, heads=64, d_k=192, d_v=128,
            window=128, hidden=4096, width=2048, held=16)


@pytest.mark.parametrize("tokens", [128, 1024], ids=["decode", "chunk"])
@pytest.mark.parametrize("k,n", [(4096, 4096), (2048, 4096)],
                         ids=["gate_up", "down"])
def test_grouped_expert_matmul_at_the_cells_shapes(v5e, tokens, k, n):
    """16 held experts of the published widths: a decode step's 128 x 8
    assignments and a prefill chunk's 1024 x 8 (the 6144 bucket is six)."""
    from paddle_tpu.ops.pallas.grouped_expert_matmul import (
        grouped_expert_matmul, padded_rows)
    sds = _on(SingleDeviceSharding(v5e[0]))
    rows = padded_rows(tokens * 8, MIMO["held"])
    assert _lower(grouped_expert_matmul, sds((rows, k), jnp.bfloat16),
                  sds((MIMO["held"], k, n), jnp.bfloat16),
                  sds((MIMO["held"],), jnp.int32)) == [
        "grouped_expert_matmul"]


@pytest.mark.parametrize("kv_heads,positions,sink", [
    (4, MIMO["positions"], False), (8, MIMO["window"], True)],
    ids=["full", "ring_with_sink"])
def test_ragged_decode_attention_over_mimo_caches(v5e, kv_heads, positions,
                                                  sink):
    """K of 192 lanes allocated as 256, V of 128, the cache as (position, KV
    head) rows: read in place, no copy of either cache."""
    from paddle_tpu.ops.pallas.decode_attention import cache_lanes
    sds = _on(SingleDeviceSharding(v5e[0]))
    b, rows = MIMO["slots"], positions * kv_heads
    args = [sds((b, 1, MIMO["heads"], MIMO["d_k"]), jnp.bfloat16),
            sds((b, rows, cache_lanes(MIMO["d_k"])), jnp.bfloat16),
            sds((b, rows, MIMO["d_v"]), jnp.bfloat16), sds((b,), jnp.int32)]
    fn = lambda q, k, v, l, *s: ragged_decode_attention(
        q, k, v, l, sink=s[0] if s else None, num_kv_heads=kv_heads)
    if sink:
        args.append(sds((MIMO["heads"],), jnp.float32))
    lowered = jax.jit(fn).lower(*args)
    assert kernel_names(lowered.as_text()) == ["ragged_decode_attention"]
    assert not _copies_of(lowered.compile().as_text(),
                          f"{b},{rows},256", f"{b},{rows},128")


def test_ragged_decode_attention_refuses_lanes_it_would_have_to_copy(v5e):
    """A cache whose lanes are not whole tiles used to be padded, so copied,
    on every call; it is refused, typed, and the message names the
    allocation that is read in place."""
    from paddle_tpu.ops.pallas.decode_attention import CacheLayoutUnsupported
    sds = _on(SingleDeviceSharding(v5e[0]))
    cache = sds((8, S, 4, 192), jnp.bfloat16)
    with pytest.raises(CacheLayoutUnsupported, match="256 lanes"):
        jax.jit(ragged_decode_attention).lower(
            sds((8, 1, 64, 192), jnp.bfloat16), cache, cache,
            sds((8,), jnp.int32))


def test_kv_cache_append_into_a_ring_of_rows(v5e):
    """A window layer's decode write: 8 KV heads of one position, K and V of
    different lanes, into the (position, KV head) rows of the ring, in
    place."""
    sds = _on(SingleDeviceSharding(v5e[0]))
    b, rows = MIMO["slots"], MIMO["window"] * 8
    lowered = jax.jit(kv_cache_append, donate_argnums=(0, 1)).lower(
        sds((b, rows, 256), jnp.bfloat16), sds((b, rows, 128), jnp.bfloat16),
        sds((b, 8, 256), jnp.bfloat16), sds((b, 8, 128), jnp.bfloat16),
        sds((b,), jnp.int32))
    assert kernel_names(lowered.as_text()) == ["kv_cache_append"]
    assert not _copies_of(lowered.compile().as_text(), f"{b},{rows},256",
                          f"{b},{rows},128")


@pytest.mark.parametrize("kv_heads,window,sink", [(4, None, False),
                                                  (8, 128, True)],
                         ids=["full", "window_with_sink"])
def test_windowed_flash_attention_at_the_longest_bucket(v5e, kv_heads,
                                                        window, sink):
    from paddle_tpu.ops.pallas.flash_attention import (
        windowed_flash_attention)
    sds = _on(SingleDeviceSharding(v5e[0]))
    s = 6144
    fn = lambda q, k, v, sk: windowed_flash_attention(
        q, k, v, sk if sink else None, window, None)
    assert _lower(fn, sds((1, s, MIMO["heads"], MIMO["d_k"]), jnp.bfloat16),
                  sds((1, s, kv_heads, MIMO["d_k"]), jnp.bfloat16),
                  sds((1, s, kv_heads, MIMO["d_v"]), jnp.bfloat16),
                  sds((MIMO["heads"],), jnp.float32)) == [
        "flash_attention_fwd"]


@pytest.mark.parametrize("tokens,kernels", [
    ((8, 1), ["grouped_expert_matmul"] * 2 + ["kv_cache_append"]
     + ["ragged_decode_attention"] * 2),
    ((1, 256), ["flash_attention_fwd"] * 2 + ["grouped_expert_matmul"] * 2),
], ids=["decode", "prefill"])
def test_mimo_slot_step(v5e, tokens, kernels):
    """The serving step as the engine builds it, at the published head
    geometry and a narrow hidden size: a full layer with the dense MLP, then
    a window layer and a full layer with experts, over two kinds of cache.
    Every kernel is jitted once a shape however many layers call it (two
    expert layers: the gate-and-up and the down matmul once each; the decode
    and the flash kernel once a kind of layer); the full layers' 4 KV heads
    are half a tile a position and keep the vmapped write, the window
    layer's 8 take the in-place row write."""
    from paddle_tpu.models import MiMoConfig, MiMoForCausalLM
    P.seed(0)
    model = MiMoForCausalLM(MiMoConfig.tiny(
        vocab=512, hidden=256, inter=512, moe_inter=256, heads=8, kv_heads=4,
        swa_kv_heads=8, head_dim=192, v_head_dim=128, window=128,
        experts=16, held=(0, 4), top_k=2, seq=S, pattern=(0, 1, 0),
        moe=(0, 1, 1)))
    model.bfloat16()
    sds = _on(SingleDeviceSharding(v5e[0]))
    like = lambda x: sds(x.shape, x.dtype)
    params = [like(p._value) for p in model.parameters()]
    b = tokens[0]
    caches = [(like(x._value), like(y._value))
              for x, y in model.init_kv_caches(b, S)]
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds(tokens, jnp.int32), caches,
                         sds((b,), jnp.int32), sds((b,), jnp.int32))
    lowered.compile()
    assert sorted(kernel_names(lowered.as_text())) == kernels


@pytest.mark.parametrize("tokens,kernels", [
    ((8, 1), ["ragged_decode_attention"] * 2 + ["ssm_decode_update"]),
    ((1, 256), ["flash_attention_fwd"] * 2 + ["ragged_decode_attention",
                                              "selective_scan"]),
], ids=["decode", "prefill"])
def test_phi4flash_slot_step(v5e, tokens, kernels):
    """The SambaY serving step as the engine builds it, at the published
    head of 64 lanes (K/V rows of 128) and a narrow hidden size, one whole
    layer map of 8 layers: Mamba, window, Mamba, window, Mamba (the
    memory), full, GMU, cross.  Decode reads the rings and the shared cache
    through one decode kernel a shape; the prefill runs the window and the
    causal flash kernel, the scan, and the decode kernel for the cross
    layer at the last position."""
    from paddle_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM
    P.seed(0)
    model = Phi4FlashForCausalLM(Phi4FlashConfig.tiny(
        vocab=512, hidden=256, inter=512, heads=4, kv_heads=4, window=128,
        d_state=16, dt_rank=16, seq=S))
    model.bfloat16()
    sds = _on(SingleDeviceSharding(v5e[0]))
    like = lambda x: sds(x.shape, x.dtype)
    params = [like(p._value) for p in model.parameters()]
    b = tokens[0]
    caches = [(like(x._value), like(y._value))
              for x, y in model.init_kv_caches(b, S)]
    capture.set_step_capture_enabled(False)      # plain jit: has .lower
    try:
        step = build_step(model, "slot")
    finally:
        capture.set_step_capture_enabled(True)
    lowered = step.lower(params, sds(tokens, jnp.int32), caches,
                         sds((b,), jnp.int32), sds((b,), jnp.int32))
    lowered.compile()
    assert sorted(kernel_names(lowered.as_text())) == sorted(kernels)
