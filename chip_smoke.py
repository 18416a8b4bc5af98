"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once through the entry points a user calls, at
Llama-7B layer geometry (hidden 4096, intermediate 11008, 32 heads of 128,
vocab 32000, sequence 2048) cut only by depth, with random weights from a
seed, in ONE process (a chip belongs to one process at a time):

  kernels  the three Pallas kernels, forward and backward, at the shapes the
           next two phases use, each against its own jnp reference
  train    LlamaForCausalLM + AdamW through build_hybrid_train_step(amp,
           remat, fused_loss) for a few steps on one fixed batch; with four
           chips visible, again on dp2 x mp2 and dp2 meshes, losses against
           one chip's
  serve    the same width in bf16 behind ServingEngine -> ServingGateway ->
           GatewayClient on a real socket; every generated token judged on
           the logits of one cache-free teacher-forced forward
  nets     no safety net fired on the way: zero capture bailouts, zero
           fallback calls, zero poisoned op-cache entries, every pass ran

No arguments.  Exits 0 only if every phase passed, and then prints as its last
line {"ok": true, "device": {...}} with the device as JAX reports it.  A run
that finds no TPU fails at once.  This is a smoke, not a benchmark: the
seconds it prints are there to size its time limit, not to be compared.
"""
from __future__ import annotations

import gc
import json
import math
import re
import sys
import threading
import time
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Sizes:
    hidden: int = 4096
    inter: int = 11008
    heads: int = 32
    vocab: int = 32000
    seq: int = 2048
    train_depth: int = 2
    train_batch: int = 2
    train_steps: int = 4          # after the compile step
    serve_depth: int = 4
    serve_slots: int = 4
    # (prompt tokens, new tokens, submitted only once decoding has begun)
    requests: tuple = ((100, 48, False), (400, 32, False), (1500, 32, False),
                       (120, 64, True), (500, 40, True))


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def say(msg: str):
    print(msg, flush=True)


def rel_err(got, want) -> float:
    """Largest absolute error over the largest reference magnitude."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


# Tolerance of a bf16 kernel against an f32 reference on the same bf16
# inputs.  bf16 keeps 8 significand bits, so one rounding is 2^-9 = 0.2%
# relative.  The kernels accumulate in f32 but round what they feed the MXU
# (probabilities, score and logit cotangents) and what they return to bf16:
# a handful of roundings per value, uncorrelated over a long sum, stays
# inside 2% of the largest magnitude; a wrong mask, scale or tile does not.
BF16_TOL = 2e-2


def require_kernels(stablehlo_text: str, names) -> list:
    from paddle_tpu.ops.pallas._common import kernel_names
    found = kernel_names(stablehlo_text)
    missing = [n for n in names if n not in found]
    check(not missing, f"compiled step lacks the Pallas kernels {missing}; "
                       f"its tpu_custom_calls are {sorted(set(found))}")
    return found


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def phase_kernels(sz: Sizes):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _sdpa_ref
    from paddle_tpu.ops.pallas.decode_attention import (
        _ragged_ref, ragged_decode_attention)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.fused_ce import fused_linear_cross_entropy

    d = sz.hidden // sz.heads
    key = jax.random.key(0)
    f32 = jnp.float32

    def exact(fn):
        # the references multiply in full f32 on the MXU (several passes)
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    # flash attention, causal, the train phase's [B, S, H, D]
    kq, kk, kv, kg, key = jax.random.split(key, 5)
    shape = (sz.train_batch, sz.seq, sz.heads, d)
    q, k, v, g = (jax.random.normal(kx, shape, f32).astype(jnp.bfloat16)
                  for kx in (kq, kk, kv, kg))

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None).astype(f32)
                       * g.astype(f32))

    def ref_loss(q, k, v):
        return jnp.sum(_sdpa_ref(q, k, v, None, 0.0, True, None) * g.astype(f32))

    up = [x.astype(f32) for x in (q, k, v)]
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, None))(q, k, v)
    want = exact(lambda q, k, v: _sdpa_ref(q, k, v, None, 0.0, True, None))(*up)
    errs = {"out": rel_err(out, want)}
    got_g = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
    want_g = exact(jax.grad(ref_loss, argnums=(0, 1, 2)))(*up)
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        errs[name] = rel_err(a, b)
    say(f"  flash_attention fwd+bwd bf16 {shape}: " + _fmt(errs))
    check(max(errs.values()) < BF16_TOL, f"flash_attention vs _sdpa_ref: {errs}")
    del out, want, got_g, want_g, up

    # ragged decode attention, the serve phase's [slots, 1, H, D] over a
    # bf16 cache, lengths from empty to full
    kq, kk, kv, key = jax.random.split(key, 4)
    b = sz.serve_slots
    q = jax.random.normal(kq, (b, 1, sz.heads, d), f32).astype(jnp.bfloat16)
    kc, vc = (jax.random.normal(kx, (b, sz.seq, sz.heads, d), f32)
              .astype(jnp.bfloat16) for kx in (kk, kv))
    lengths = jnp.asarray(
        ([0, 1, sz.seq, sz.seq // 2 + 3, 257, 100, 1531, 64] * b)[:b],
        jnp.int32)
    out = jax.jit(ragged_decode_attention)(q, kc, vc, lengths)
    want = exact(lambda *a: _ragged_ref(*a, 1.0 / math.sqrt(d)))(
        q, kc, vc, lengths)
    err = rel_err(out, want)
    say(f"  ragged_decode_attention bf16 q{q.shape} cache{kc.shape} "
        f"lengths {lengths.tolist()}: out {err:.2e}")
    check(err < BF16_TOL, f"ragged_decode_attention vs _ragged_ref: {err}")
    del out, want, kc, vc

    # fused lm-head + CE at the 7B head, the train phase's [B*S, H] x [H, V]
    kh, kw, kl, kg, key = jax.random.split(key, 5)
    n = sz.train_batch * sz.seq
    h = jax.random.normal(kh, (n, sz.hidden), f32).astype(jnp.bfloat16)
    w = (0.02 * jax.random.normal(kw, (sz.hidden, sz.vocab), f32)
         ).astype(jnp.bfloat16)
    lab = jax.random.randint(kl, (n,), 0, sz.vocab, jnp.int32)
    g = jax.random.normal(kg, (n,), f32)

    def ce_ref(h, w):
        s = h @ w
        return (jax.scipy.special.logsumexp(s, axis=-1)
                - jnp.take_along_axis(s, lab[:, None], axis=1)[:, 0])

    loss = jax.jit(fused_linear_cross_entropy)(h, w, lab)
    want = exact(ce_ref)(h.astype(f32), w.astype(f32))
    errs = {"loss": rel_err(loss, want)}
    got_g = jax.jit(jax.grad(
        lambda h, w: jnp.sum(fused_linear_cross_entropy(h, w, lab) * g),
        argnums=(0, 1)))(h, w)
    want_g = exact(jax.grad(lambda h, w: jnp.sum(ce_ref(h, w) * g),
                            argnums=(0, 1)))(h.astype(f32), w.astype(f32))
    for name, a, b_ in zip(("dh", "dw"), got_g, want_g):
        errs[name] = rel_err(a, b_)
    say(f"  fused_linear_cross_entropy fwd+bwd bf16 h{h.shape} w{w.shape}: "
        + _fmt(errs))
    check(max(errs.values()) < BF16_TOL,
          f"fused_linear_cross_entropy vs logsumexp CE: {errs}")


def _fmt(errs: dict) -> str:
    return " ".join(f"{k} {v:.2e}" for k, v in errs.items())


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def _llama_config(sz: Sizes, depth: int):
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                       intermediate_size=sz.inter,
                       num_hidden_layers=depth,
                       num_attention_heads=sz.heads,
                       max_position_embeddings=sz.seq)


TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "fused_ce_fwd", "fused_ce_bwd_dh",
                 "fused_ce_bwd_dw")


def _train(sz: Sizes, mesh, batch_size: int):
    """Build the hybrid step on `mesh` (None: one chip) and take
    1 + train_steps steps on one fixed seeded batch; returns the losses."""
    import jax

    import paddle_tpu as P
    from paddle_tpu.models import LlamaForCausalLM, build_hybrid_train_step

    P.seed(0)
    model = LlamaForCausalLM(_llama_config(sz, sz.train_depth))
    opt = P.optimizer.AdamW(learning_rate=3e-4,
                            parameters=model.parameters())
    step = build_hybrid_train_step(model, opt, mesh=mesh, amp=True,
                                   remat=True, fused_loss=True)
    ids = np.random.RandomState(0).randint(0, sz.vocab,
                                           (batch_size, sz.seq + 1))
    batch = {"input_ids": P.to_tensor(ids[:, :-1]),
             "labels": P.to_tensor(ids[:, 1:])}

    text = step.lower_text(batch)
    mp = mesh.shape.get("mp", 1) if mesh is not None else 1
    # the fused loss is gated to mp == 1 (a vocab-sharded head runs in GSPMD)
    found = require_kernels(text, TRAIN_KERNELS if mp == 1
                            else TRAIN_KERNELS[:3])
    say(f"  step HLO: {len(found)} tpu_custom_calls: "
        + ", ".join(sorted(set(found))))
    if mesh is not None:
        sig = next(l for l in text.splitlines() if "@main(" in l)
        rows = re.findall(
            rf"tensor<{batch_size}x{sz.seq}xi64> \{{[^}}]*sharding<@mesh, "
            r'\[\{"dp"\}', sig)
        check(len(rows) == 2, "input_ids and labels are not sharded over "
                              f"'dp' in the step's signature: {sig[-400:]}")

    t0 = time.perf_counter()
    losses = [float(step(batch).numpy())]
    say(f"  compile + first step: {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    for _ in range(sz.train_steps):
        losses.append(float(step(batch).numpy()))
    say(f"  {sz.train_steps} more steps: {time.perf_counter() - t0:.2f} s; "
        f"losses " + " ".join(f"{l:.4f}" for l in losses))
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    check(abs(losses[0] - math.log(sz.vocab)) < 1.0,
          f"first loss {losses[0]:.3f} is not near ln {sz.vocab} = "
          f"{math.log(sz.vocab):.3f} (random weights predict ~uniformly)")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")

    if mesh is not None:
        n = math.prod(mesh.shape.values())
        leaves = jax.tree_util.tree_leaves(step.state["params"])
        split = [l for l in leaves if not l.sharding.is_fully_replicated]
        check(all(len(l.sharding.device_set) == n for l in leaves),
              f"parameters are not on all {n} devices")
        check(bool(split) == (mp > 1) and all(
            math.prod(l.addressable_shards[0].data.shape) * mp
            == math.prod(l.shape) for l in split),
            f"parameters split over 'mp' = {mp}: {len(split)} arrays")
        say(f"  {len(split)} of {len(leaves)} parameter arrays split over "
            f"'mp', all on {n} devices; batch rows split over 'dp'")
    return losses


def phase_train(sz: Sizes, n_devices: int):
    from paddle_tpu.parallel import mesh as mesh_mod

    say(f"  one chip: depth {sz.train_depth}, batch {sz.train_batch} x "
        f"{sz.seq}, AdamW, amp + remat + fused loss")
    one = _train(sz, None, sz.train_batch)
    gc.collect()
    if n_devices < 4:
        say(f"  {n_devices} chip(s) visible: the meshed steps are not run")
        return
    # The same seed and global batch: parallelism must not change the loss
    # beyond the order of bf16 reductions (row-split batch, column-split
    # matmuls) and, under mp, the unfused f32 CE.  dp2 x mp2 is the hybrid
    # step; dp2 alone keeps the fused loss, which is gated to mp == 1.
    for shape in ({"dp": 2, "mp": 2}, {"dp": 2, "mp": 1}):
        say(f"  mesh {shape}: depth {sz.train_depth}, batch "
            f"{sz.train_batch} x {sz.seq}")
        mesh = mesh_mod.init_mesh(shape)
        try:
            many = _train(sz, mesh, sz.train_batch)
        finally:
            mesh_mod.set_mesh(None)
        gc.collect()
        worst = max(abs(a - b) for a, b in zip(one, many))
        say(f"  loss, mesh {shape} vs one chip: largest difference "
            f"{worst:.4f}")
        check(worst < 5e-2,
              f"mesh {shape} losses {many} differ from one chip's {one}")


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def phase_serve(sz: Sizes):
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.autograd.grad_mode import no_grad
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.serving.gateway import (GatewayClient,
                                                      ServingGateway)
    from paddle_tpu.models import LlamaForCausalLM

    P.seed(1)
    model = LlamaForCausalLM(_llama_config(sz, sz.serve_depth))
    model.bfloat16()
    model.eval()
    say(f"  depth {sz.serve_depth}, bf16, {sz.serve_slots} slots x {sz.seq} "
        f"positions, {len(sz.requests)} requests "
        f"(prompt, new, late): {list(sz.requests)}")
    eng = ServingEngine(model, max_batch=sz.serve_slots, max_seq_len=sz.seq)
    gw = ServingGateway(eng)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, sz.vocab, (p,)) for p, _, _ in sz.requests]
    results: list = [None] * len(prompts)
    # every request owns a connection: one client carries one exchange at a
    # time.  The budget covers the compiles queued ahead of a request.
    budget = 900.0

    def ask(i):
        try:
            cli = GatewayClient("127.0.0.1", gw.port)
            try:
                results[i] = cli.generate(prompts[i],
                                          max_new_tokens=sz.requests[i][1],
                                          ttl=budget)
            finally:
                cli.close()
        except BaseException as e:  # noqa: BLE001 — reported by the main thread
            results[i] = e

    threads = [threading.Thread(target=ask, args=(i,), daemon=True)
               for i in range(len(prompts))]
    t0 = time.perf_counter()
    try:
        for t, (_, _, late) in zip(threads, sz.requests):
            if not late:
                t.start()
        while eng.info()["decode_steps"] < 4:      # decoding has begun
            check(time.perf_counter() - t0 < budget,
                  "the engine took no decode step")
            check(any(t.is_alive() for t in threads),
                  f"every early request ended before decoding: {results}")
            time.sleep(0.05)
        say(f"  decoding began after {time.perf_counter() - t0:.1f} s "
            "(prefill and decode compiles included); submitting the late "
            "requests")
        for t, (_, _, late) in zip(threads, sz.requests):
            if late:
                t.start()
        for t in threads:
            t.join(timeout=budget)
            check(not t.is_alive(), "a request did not return")
    finally:
        drained = gw.stop(drain=True)
    say(f"  all requests answered after {time.perf_counter() - t0:.1f} s")
    check(drained is True, "gw.stop(drain=True) did not drain")
    for r in results:
        if isinstance(r, BaseException):
            raise r

    info, ginfo = eng.info(), gw.info()
    check(info["finished"] == len(prompts) and info["timed_out"] == 0
          and info["rejected"] == 0, f"engine counters: {info}")
    check(ginfo["errors"] == 0 and ginfo["driver_errors"] == 0,
          f"gateway counters: {ginfo}")

    progs = [p for p in eng._step_fn.programs()
             if (sz.serve_slots, 1) in [a.shape for a in p.in_avals]]
    check(len(progs) == 1, "no captured program at the [slots, 1] decode "
                           f"signature: {eng._step_fn.cache_info()}")
    # the decode step, as captured: its HLO holds the ragged kernel and the
    # in-place K/V row write (a fall back to the scatter would still serve)
    serve_kernels = ["kv_cache_append", "ragged_decode_attention"]
    found = require_kernels(progs[0].lower_text(), serve_kernels)
    # the row write is one call a layer; the decode kernel is one jitted
    # function that every layer calls, so the module holds its body once
    check(found.count("kv_cache_append") == sz.serve_depth
          and set(found) == set(serve_kernels), f"decode step: {found}")
    check(info["step"]["kv_write"] == {"kernel": sz.serve_depth,
                                       "scatter": 0},
          f"engine counters: {info['step']}")
    say(f"  decode step HLO: {len(found)} tpu_custom_calls "
        f"({sz.serve_depth} x kv_cache_append, "
        f"{found.count('ragged_decode_attention')} x "
        f"ragged_decode_attention); "
        f"{info['decode_steps']} decode steps, {info['prefills']} prefills, "
        f"lowerings {info['step']['lowerings']}")

    # Judge on logits, not tokens: random weights leave near-ties that
    # rounding breaks differently between batch shapes and attention paths
    # (bucketed masked prefill, ragged decode kernel, flash kernel here).
    # Teacher-force each sequence, right-padded to one length (causal: the
    # padding cannot reach back), through ONE cache-free forward; a
    # generated token must be the argmax at its position or within MARGIN
    # of it, as a share of the row's logit range.  bf16 activations carry
    # ~2^-8 relative noise per layer, which moves a logit by well under 1%
    # of a range that spans ~8 standard deviations of 32000 logits; a token
    # from a wrong cache row or position misses by a large part of it.
    margin = 0.03
    exact_n = near_n = 0
    for (plen, _, _), prompt, seq in zip(sz.requests, prompts, results):
        check(seq.shape[0] > plen and np.array_equal(seq[:plen], prompt),
              "a response does not start with its prompt")
        ids = np.zeros((1, sz.seq), np.int64)
        ids[0, :seq.shape[0]] = seq
        with no_grad():
            logits = model(P.Tensor(jnp.asarray(ids)))._value[0]
        rows = np.asarray(logits[plen - 1:seq.shape[0] - 1].astype(jnp.float32))
        check(rows.shape[1] == sz.vocab and np.isfinite(rows).all(),
              f"teacher-forced logits: shape {rows.shape}")
        top = rows.max(axis=1)
        gap = (top - rows[np.arange(len(rows)), seq[plen:]]) \
            / (top - rows.min(axis=1))
        check((gap <= margin).all(),
              f"prompt of {plen}: generated tokens {np.flatnonzero(gap > margin)} "
              f"miss the teacher-forced argmax by {gap.max():.3f} of the "
              f"logit range (margin {margin})")
        exact_n += int((gap == 0).sum())
        near_n += int(((gap > 0) & (gap <= margin)).sum())
    say(f"  teacher-forced check: {exact_n} generated tokens are the argmax, "
        f"{near_n} within {margin:.0%} of the logit range of it, 0 beyond")
    return eng


# ---------------------------------------------------------------------------
# phase: no net fired
# ---------------------------------------------------------------------------

def phase_nets(eng):
    from paddle_tpu.jit import capture
    from paddle_tpu.jit.passes import default_passes
    from paddle_tpu.ops import dispatch

    cap, step, ops = (capture.capture_info(), eng.info()["step"],
                      dispatch.cache_info())
    say(f"  capture tier: {cap['lowerings']} lowerings, {cap['hits']} hits, "
        f"{cap['bailouts']} bailouts, {cap['fallback_calls']} fallback calls")
    check(cap["enabled"] and cap["bailouts"] == 0
          and cap["fallback_calls"] == 0, f"capture tier: {cap}")
    check(step["bailouts"] == 0 and step["fallback_calls"] == 0
          and step["lowerings"] > 0, f"engine step: {step}")
    progs = eng._step_fn.programs()
    check(len(progs) == step["lowerings"],
          "a captured step has no program (captured_program is None)")
    for p in progs:
        check(tuple(p.pass_report.passes_run) == default_passes(),
              f"passes run: {p.pass_report.passes_run}")
    say(f"  every captured program ran passes {list(default_passes())}")
    poisoned = {k: v["bailouts"] for k, v in ops["per_op"].items()
                if v["bailouts"]}
    say(f"  op cache: {ops['hits']} hits over {len(ops['per_op'])} ops, "
        f"{ops['bailouts']} poisoned entries")
    check(not poisoned, f"poisoned op-cache entries: {poisoned}")


# ---------------------------------------------------------------------------

def main():
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU: jax found {device}")

    from importlib.metadata import version

    from paddle_tpu.utils.compile_cache import configure_compile_cache

    say(f"device: {device}")
    say(f"jax {version('jax')}, jaxlib {version('jaxlib')}, "
        f"libtpu {version('libtpu')}")
    say(f"compile cache: {configure_compile_cache()}")

    sz = Sizes()
    t_all = time.perf_counter()

    def phase(name, run, *args):
        say(f"[{name}]")
        t0 = time.perf_counter()
        out = run(*args)
        gc.collect()
        say(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
        return out

    phase("kernels", phase_kernels, sz)
    phase("train", phase_train, sz, device["count"])
    eng = phase("serve", phase_serve, sz)
    phase("no net fired", phase_nets, eng)
    say(f"all phases passed in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
