"""Microbenchmark: continuous batching vs sequential per-request generate(),
plus speculative decoding (spec-on vs spec-off) under --spec.

Default mode measures the serving engine (paddle_tpu/inference/serving)
against the baseline it replaces — one `model.generate()` call per
request, back to back — on the SAME mixed-length workload and the SAME
tiny llama config. CPU-runnable ("backend": "cpu-proxy", same convention
as bench.py) so the number stays measurable when the TPU probe reports
tpu-unavailable:

  sequential — for each request: prefill + per-token KV-cache decode at
               batch 1 (each token is one whole-step-captured executable
               call serving ONE sequence).
  continuous — the ServingEngine: same executables, but every decode step
               serves every active slot, with requests joining/leaving
               between steps as they arrive/finish.

--spec mode measures speculative decoding: the SAME engine and the SAME
workload with the n-gram drafter proposing PT_SERVE_BENCH_SPEC_K tokens
per slot against the engine with speculation off. The model ties its
lm head to the embedding (standard weight tying): a random UNTIED tiny
model emits streams with no local structure at all — nothing any drafter
could exploit — while the tied model produces the run/cycle-heavy
streams that stand in for a real LM's locally-predictable spans (the
regime prompt-lookup decoding targets). The acceptance rate is part of
the payload precisely because the speedup is a function of it.

Prints ONE JSON line per mode:
  {"metric": "serving_throughput_speedup_vs_sequential", "value": <x>, ...}
  {"metric": "serving_spec_speedup_vs_nonspec", "value": <x>,
   "acceptance_rate": ..., "tokens_per_verify": ..., ...}
(acceptance floors: 1.5x and 1.25x) and writes a BENCH_SELF_SERVE_<ts>
artifact with the full workload, engine.info() counters (occupancy, pool,
lowerings, speculative funnel), and the latency distribution including
time-to-first-token p50/p99 (submission -> first emitted token, queueing
included — the honest serving number).

The workload keeps the queue deeper than the batch (requests >> slots)
— the serving regime continuous batching exists for; a trickle workload
(queue < batch) degenerates to sequential-with-padding and measures ~1x
on a CPU where tiny-model decode is compute-bound, not dispatch-bound.
The --spec workload decodes longer (48-96 new tokens) because that is
the regime speculation serves: decode-dominated traffic.

--overload mode measures the serving front door under 2x-over-capacity
open-loop load THROUGH the gateway wire: every request is its own client
thread, capacity (slots + queue) covers half the burst, and the rest must
be shed with a typed 429 — fast (shed p99 rides the payload; the slow
battery pins < 50 ms), never a hang, never an untyped error. The two
acceptance floors: accepted requests' tokens stay BITWISE the
closed-loop engine's, and goodput (accepted tokens/s) stays >= 0.8x the
closed-loop engine that was never overloaded — the overload machinery
(admission checks, the degradation ladder) may shed load, not throughput.
Ladder occupancy (fraction of steps at each pressure level) rides the
payload so a ladder that never engages — or never disengages — is
diagnosable from the artifact.

Env: PT_SERVE_BENCH_REQUESTS (default 24), PT_SERVE_BENCH_BATCH (8),
     PT_SERVE_BENCH_REPS (3), PT_SERVE_BENCH_SPEC_K (6).
"""
from __future__ import annotations

import json
import os
import sys
import time

# steady-state dispatch is the subject, not compile thrash: sequential
# generate() lowers one (prefill, decode) pair PER DISTINCT request shape
# (its cache is sized prompt+new), so the mixed workload needs more step-
# capture signatures than the default 16-entry LRU or the sequential leg
# measures retracing instead of serving
os.environ.setdefault("PT_STEP_CAPTURE_SIZE", "128")

import jax

# serving-loop overhead is the subject — always on the CPU, and never holding
# the chip whatever JAX_PLATFORMS says
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as P  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402

MAX_SEQ = 64        # sized to the workload: 28 prompt + 32 new <= 64
SPEC_MAX_SEQ = 128  # --spec decodes longer: 28 + 96 + k hits 128 (clamped)


def _build(seq=MAX_SEQ, tie=False):
    P.seed(0)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           inter=128, seq=seq)
    model = LlamaForCausalLM(cfg)
    if tie:
        # weight tying (lm_head = embedding^T): gives the random proxy
        # model locally-predictable output structure — see module docstring
        model.lm_head.weight._value = model.llama.embed_tokens.weight._value.T
    return model, cfg


def _workload(n, vocab, seed=0, new_lo=16, new_hi=33, seq=MAX_SEQ, spec_k=0):
    """Mixed-length: prompts 4..28 tokens, new_lo..new_hi-1 new tokens."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = int(rng.randint(4, 29))
        new = int(rng.randint(new_lo, new_hi))
        new = min(new, seq - plen - spec_k)
        out.append((rng.randint(0, vocab, (plen,)), new))
    return out


def _percentiles(vals_ms):
    arr = np.asarray(vals_ms)
    return (float(np.percentile(arr, 50)), float(np.percentile(arr, 99)))


def _run_sequential(model, work):
    outs = []
    token_times = []
    t0 = time.perf_counter()
    for prompt, new in work:
        tprev = time.perf_counter()
        ids = P.to_tensor(prompt.reshape(1, -1))
        out = model.generate(ids, max_new_tokens=new)
        tend = time.perf_counter()
        outs.append(np.asarray(out.numpy())[0])
        # generate() is opaque per-token; spread the call time uniformly
        # (an upper bound on its p50, fair since its tokens are serial)
        token_times += [(tend - tprev) / new] * new
    wall = time.perf_counter() - t0
    n_tokens = sum(new for _, new in work)
    return outs, n_tokens / wall, token_times


def _run_engine(model, work, batch, max_seq, spec_k=0):
    eng = ServingEngine(model, max_batch=batch, max_seq_len=max_seq,
                        spec_k=spec_k, drafter="ngram" if spec_k else None)
    t0 = time.perf_counter()
    reqs = [eng.submit(prompt, max_new_tokens=new) for prompt, new in work]
    eng.run()
    wall = time.perf_counter() - t0
    outs = [r.result() for r in reqs]
    # per-token inter-arrival latency per request (first token measured
    # from submission — includes queueing, the honest serving number) and
    # time-to-first-token per request
    lat, ttft = [], []
    for r in reqs:
        prev = r.submit_time
        ttft.append(r.token_times[0] - r.submit_time)
        for t in r.token_times:
            lat.append(t - prev)
            prev = t
    n_tokens = sum(len(r.output_tokens) for r in reqs)
    return outs, n_tokens / wall, lat, ttft, eng


def _artifact(payload, detail):
    ts = time.strftime("%Y%m%d_%H%M%S")
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"BENCH_SELF_SERVE_{ts}.json")
    try:
        with open(path, "w") as f:
            json.dump({**payload, "detail": detail}, f, indent=1)
        print(f"# artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# artifact write failed: {e}", file=sys.stderr)


def main() -> dict:
    n_requests = int(os.environ.get("PT_SERVE_BENCH_REQUESTS", "24"))
    batch = int(os.environ.get("PT_SERVE_BENCH_BATCH", "8"))
    reps = int(os.environ.get("PT_SERVE_BENCH_REPS", "3"))

    model, cfg = _build()
    work = _workload(n_requests, cfg.vocab_size)

    # warmup: one FULL pass of each path so every lowering both sides use
    # (sequential's per-shape pairs, the engine's prefill buckets and the
    # batched decode) is compiled off the clock — steady-state throughput
    # is the metric, compile latency is whole-step capture's own bench
    _run_sequential(model, work)
    _run_engine(model, work, batch, MAX_SEQ)

    # best-of-reps: single shared core, the best rep is the noise floor
    best_seq = (None, 0.0, None)
    best_cont = None
    for _ in range(reps):
        s = _run_sequential(model, work)
        if s[1] > best_seq[1]:
            best_seq = s
        c = _run_engine(model, work, batch, MAX_SEQ)
        if best_cont is None or c[1] > best_cont[1]:
            best_cont = c
    seq_outs, seq_tps, _ = best_seq
    cont_outs, cont_tps, lat, ttft, eng = best_cont

    # trace-on leg (the observability cost gate): the SAME engine
    # workload with PT_TRACE flipped on — spans per decode step + the
    # scheduler/submit events are the only delta. Best-of-reps like the
    # untraced leg so the ratio compares noise floors, not noise.
    # Documented ceiling: <= 1.25x (slow battery; smoke allows 1.5x).
    from paddle_tpu.observability import trace as obs_trace

    obs_trace.enable(True)
    try:
        traced_tps = -1.0   # the first rep always lands, even at 0 tps
        traced_outs = None
        for _ in range(reps):
            c = _run_engine(model, work, batch, MAX_SEQ)
            if c[1] > traced_tps:
                traced_tps, traced_outs = c[1], c[0]
    finally:
        obs_trace.enable(False)
        obs_trace.trace_clear()
    trace_overhead = cont_tps / traced_tps if traced_tps > 0 else 0.0

    # correctness gate: the engine must emit EXACTLY the oracle's tokens
    # (traced leg included — spans must never perturb the math)
    mismatches = sum(1 for a, b in zip(seq_outs, cont_outs)
                     if a.shape != b.shape or not (a == b).all())
    mismatches += sum(1 for a, b in zip(seq_outs, traced_outs)
                      if a.shape != b.shape or not (a == b).all())

    p50, p99 = _percentiles(np.asarray(lat) * 1e3)
    ttft50, ttft99 = _percentiles(np.asarray(ttft) * 1e3)
    speedup = cont_tps / seq_tps if seq_tps else 0.0
    info = eng.info()

    payload = {
        "metric": "serving_throughput_speedup_vs_sequential",
        "value": round(speedup, 2),
        "unit": "x",
        # acceptance floor: continuous >= 1.5x sequential tokens/s
        "vs_baseline": round(speedup / 1.5, 4),
        "backend": "cpu-proxy",
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "continuous_tokens_per_sec": round(cont_tps, 1),
        "p50_token_ms": round(p50, 2),
        "p99_token_ms": round(p99, 2),
        "ttft_p50_ms": round(ttft50, 2),
        "ttft_p99_ms": round(ttft99, 2),
        "requests": n_requests,
        "max_batch": batch,
        "avg_occupancy": round(info["avg_occupancy"], 3),
        "token_mismatches": mismatches,
        # trace-on / trace-off throughput ratio (documented ceiling 1.25x)
        "trace_overhead": round(trace_overhead, 4),
        "traced_tokens_per_sec": round(traced_tps, 1),
    }
    print(json.dumps(payload), flush=True)

    detail = {
        "workload": [{"prompt_len": int(p.size), "max_new": n}
                     for p, n in work],
        "engine_info": info,
        "latency_ms": {"p50": p50, "p99": p99,
                       "ttft_p50": ttft50, "ttft_p99": ttft99},
    }
    _artifact(payload, detail)
    return payload


def spec_main() -> dict:
    """--spec: speculative (n-gram drafter) vs non-speculative engine on
    one decode-dominated workload over the weight-tied proxy model.

    Default batch is 4 (vs the throughput bench's 8): speculation trades
    per-step fixed cost (dispatch, host loop, token sync) for window
    compute, so its win is largest where steps are overhead-bound — small
    decode batches on this CPU proxy, memory-bound decode on a real TPU.
    At batch 16 the [B, k+1] window's COMPUTE dominates the step and the
    CPU proxy measures ~1x; the knob is exposed so the crossover is
    reproducible."""
    n_requests = int(os.environ.get("PT_SERVE_BENCH_REQUESTS", "24"))
    batch = int(os.environ.get("PT_SERVE_BENCH_BATCH", "4"))
    reps = int(os.environ.get("PT_SERVE_BENCH_REPS", "3"))
    spec_k = int(os.environ.get("PT_SERVE_BENCH_SPEC_K", "6"))

    model, cfg = _build(seq=SPEC_MAX_SEQ, tie=True)
    work = _workload(n_requests, cfg.vocab_size, new_lo=48, new_hi=97,
                     seq=SPEC_MAX_SEQ, spec_k=spec_k)

    _run_engine(model, work, batch, SPEC_MAX_SEQ)                 # warm off
    _run_engine(model, work, batch, SPEC_MAX_SEQ, spec_k=spec_k)  # warm on

    best_off = best_on = None
    for _ in range(reps):
        off = _run_engine(model, work, batch, SPEC_MAX_SEQ)
        if best_off is None or off[1] > best_off[1]:
            best_off = off
        on = _run_engine(model, work, batch, SPEC_MAX_SEQ, spec_k=spec_k)
        if best_on is None or on[1] > best_on[1]:
            best_on = on
    off_outs, off_tps, off_lat, off_ttft, off_eng = best_off
    on_outs, on_tps, on_lat, on_ttft, on_eng = best_on

    # the exactness gate: speculative greedy output must be BITWISE the
    # non-speculative engine's (which PR 7 pinned to sequential generate)
    mismatches = sum(1 for a, b in zip(off_outs, on_outs)
                     if a.shape != b.shape or not (a == b).all())

    p50_on, p99_on = _percentiles(np.asarray(on_lat) * 1e3)
    ttft50_on, ttft99_on = _percentiles(np.asarray(on_ttft) * 1e3)
    ttft50_off, ttft99_off = _percentiles(np.asarray(off_ttft) * 1e3)
    speedup = on_tps / off_tps if off_tps else 0.0
    spec = on_eng.info()["spec"]

    payload = {
        "metric": "serving_spec_speedup_vs_nonspec",
        "value": round(speedup, 2),
        "unit": "x",
        # acceptance floor: speculative >= 1.25x the spec-off engine
        "vs_baseline": round(speedup / 1.25, 4),
        "backend": "cpu-proxy",
        "drafter": "ngram",
        "spec_k": spec_k,
        "acceptance_rate": round(spec["acceptance_rate"], 3),
        "tokens_per_verify": round(spec["tokens_per_verify"], 2),
        "nonspec_tokens_per_sec": round(off_tps, 1),
        "spec_tokens_per_sec": round(on_tps, 1),
        "p50_token_ms": round(p50_on, 2),
        "p99_token_ms": round(p99_on, 2),
        "ttft_p50_ms": round(ttft50_on, 2),
        "ttft_p99_ms": round(ttft99_on, 2),
        "requests": n_requests,
        "max_batch": batch,
        "token_mismatches": mismatches,
    }
    print(json.dumps(payload), flush=True)

    detail = {
        "workload": [{"prompt_len": int(p.size), "max_new": n}
                     for p, n in work],
        "spec_engine_info": on_eng.info(),
        "nonspec_engine_info": off_eng.info(),
        "ttft_ms": {"spec_p50": ttft50_on, "spec_p99": ttft99_on,
                    "nonspec_p50": ttft50_off, "nonspec_p99": ttft99_off},
    }
    _artifact(payload, detail)
    return payload


def shared_main() -> dict:
    """--shared-prefix: N requests over ONE long system prompt (the
    millions-of-users common case) against the prefix-sharing engine vs
    the unshared one, plus a mega-prompt + decode-batch leg proving
    chunked prefill bounds the max inter-decode-step gap.

    Leg 1 emits the prefill-pages-saved ratio (shared pages the borrowers
    skipped / full-prompt pages the unshared engine prefills — accounting,
    so it is deterministic at any scale) and TTFT p50/p99 for both
    engines, with the bitwise token gate across shared/unshared.

    Leg 2 streams one in-flight decode request while a mega-prompt joins:
    with PT_SERVE_PREFILL_CHUNK-style chunking the prompt prefills in
    fixed [1, chunk] windows interleaved with decode steps, so the decode
    stream's max inter-token gap stays under the single-chunk bound
    (measured: 3x the mean chunk time + 2x the mean decode step — one
    engine step is exactly one window plus one decode); the unchunked
    engine eats the whole prefill in one gap. Both gaps ride the payload.

    Env: PT_SERVE_BENCH_REQUESTS (default 8), PT_SERVE_BENCH_PREFIX (48),
         PT_SERVE_BENCH_CHUNK (8)."""
    n_requests = int(os.environ.get("PT_SERVE_BENCH_REQUESTS", "8"))
    prefix_len = int(os.environ.get("PT_SERVE_BENCH_PREFIX", "48"))
    chunk = int(os.environ.get("PT_SERVE_BENCH_CHUNK", "8"))
    page = 16
    new_tokens = 8

    model, cfg = _build(seq=SPEC_MAX_SEQ)
    rng = np.random.RandomState(11)
    common = rng.randint(0, cfg.vocab_size, (prefix_len,))
    work = [np.concatenate([common,
                            rng.randint(0, cfg.vocab_size, (2 + i % 5,))])
            for i in range(n_requests)]

    def run(sharing: bool):
        eng = ServingEngine(model, max_batch=4, max_seq_len=SPEC_MAX_SEQ,
                            page_size=page, prefix_sharing=sharing)
        outs, ttft = [], []
        # arrival order: the first request is the donor (its commit is
        # what makes every later walk hit), the rest stream in behind it
        for p in work:
            r = eng.submit(p, max_new_tokens=new_tokens)
            eng.run()
            outs.append(r.result())
            ttft.append((r.token_times[0] - r.submit_time) * 1e3)
        return outs, ttft, eng

    run(False)  # warm every lowering off the clock
    base_outs, base_ttft, base_eng = run(False)
    run(True)
    shr_outs, shr_ttft, shr_eng = run(True)

    mismatches = sum(1 for a, b in zip(base_outs, shr_outs)
                     if a.shape != b.shape or not (a == b).all())
    info = shr_eng.info()
    prompt_pages = sum(int(p.size) // page for p in work)
    saved = info["prefill_pages_saved"]
    ratio = prompt_pages / max(1, prompt_pages - saved)

    # ---- leg 2: mega-prompt vs the decode batch (own longer-sequence
    # model: the stall the chunking bounds must dwarf a decode step) ----
    gap_model, gap_cfg = _build(seq=512)
    gap_seq = 512

    def gap_leg(use_chunk):
        eng = ServingEngine(gap_model, max_batch=4, max_seq_len=gap_seq,
                            page_size=page,
                            prefill_chunk=chunk if use_chunk else 0)
        ra = eng.submit(work[0][:6], max_new_tokens=48)
        for _ in range(4):
            eng.step()
        # chunk time measured over the mega-prompt's windows ONLY: ra's
        # classic bucketed prefill above is excluded, so the single-chunk
        # bound below cannot be inflated by non-chunk prefill cost
        t_pref0, n_chunks0 = eng._prefill_time, \
            eng._counters["prefill_chunks"]
        mega = rng.randint(0, gap_cfg.vocab_size, (gap_seq - 64,))
        eng.submit(mega, max_new_tokens=4)
        eng.run()
        gaps = np.diff(np.asarray(ra.token_times)) * 1e3
        n_chunks = eng._counters["prefill_chunks"] - n_chunks0
        chunk_ms = (1e3 * (eng._prefill_time - t_pref0) / n_chunks
                    if n_chunks else 0.0)
        return float(gaps.max()), chunk_ms, eng

    gap_leg(True)   # warm the window signature...
    gap_leg(False)  # ...and the mega-prompt's bucket, so BOTH gaps
    # measure prefill stall, not compile latency
    chunked_gap, chunk_ms, ceng = gap_leg(True)
    unchunked_gap, _, _ = gap_leg(False)
    ci = ceng.info()
    decode_ms = (ci["decode_steps"] and
                 1e3 * ceng._decode_time / ci["decode_steps"]) or 0.0
    bound_ms = 3.0 * chunk_ms + 2.0 * decode_ms

    payload = {
        "metric": "serving_shared_prefix_pages_saved",
        "value": round(ratio, 2),
        "unit": "x",
        # acceptance floor: >= 2x prefill-pages-saved at 8 shared requests
        "vs_baseline": round(ratio / 2.0, 4),
        "backend": "cpu-proxy",
        "requests": n_requests,
        "prefix_len": prefix_len,
        "pages_saved": int(saved),
        "prompt_pages": int(prompt_pages),
        "token_mismatches": mismatches,
        "ttft_p50_ms_shared": round(float(np.percentile(shr_ttft, 50)), 2),
        "ttft_p99_ms_shared": round(float(np.percentile(shr_ttft, 99)), 2),
        "ttft_p50_ms_unshared": round(float(np.percentile(base_ttft, 50)),
                                      2),
        "ttft_p99_ms_unshared": round(float(np.percentile(base_ttft, 99)),
                                      2),
        "chunk": chunk,
        "chunked_max_gap_ms": round(chunked_gap, 2),
        "unchunked_max_gap_ms": round(unchunked_gap, 2),
        "single_chunk_bound_ms": round(bound_ms, 2),
        "chunked_gap_ok": bool(chunked_gap <= bound_ms),
    }
    print(json.dumps(payload), flush=True)
    _artifact(payload, {
        "workload": [{"prompt_len": int(p.size)} for p in work],
        "shared_engine_info": info,
        "unshared_engine_info": base_eng.info(),
        "chunked_engine_info": ci,
    })
    return payload


def overload_main() -> dict:
    """--overload: 2x-over-capacity burst through the gateway wire.

    Default batch is 4 with a queue of the same depth: capacity 8, burst
    16 (PT_SERVE_BENCH_REQUESTS caps the burst at an even number). One
    thread per request fires simultaneously with retries=0, so every
    admission decision is measured exactly once — accepted requests wait
    for their tokens, shed ones must get the typed 429 back immediately
    (no model compute sits on the shed path)."""
    import threading

    from paddle_tpu.inference.serving.gateway import (GatewayClient,
                                                      ServingGateway)
    from paddle_tpu.utils.deadline import EngineOverloaded

    offered = int(os.environ.get("PT_SERVE_BENCH_REQUESTS", "24"))
    offered -= offered % 2
    batch = int(os.environ.get("PT_SERVE_BENCH_BATCH", "4"))
    reps = int(os.environ.get("PT_SERVE_BENCH_REPS", "3"))
    max_queue = max(1, offered // 2 - batch)   # slots + queue = burst / 2

    model, cfg = _build()
    work = _workload(offered, cfg.vocab_size, new_lo=8, new_hi=17)

    # closed-loop reference on an engine that is NEVER overloaded: the
    # oracle token streams (greedy decode is deterministic per prompt
    # regardless of batch composition — pinned by the serving suite) and
    # the goodput baseline. The first pass doubles as warmup: the
    # whole-step capture cache is process-global, so the gateway engine
    # below reuses every lowering and the overloaded leg measures
    # serving, not compiling. Best-of-reps on BOTH sides (the ratio
    # compares noise floors, not noise — the bench's convention).
    oracle = None
    ref_tps = 0.0
    for _ in range(reps + 1):           # +1: the warmup pass
        t0 = time.perf_counter()
        ref = ServingEngine(model, max_batch=batch, max_seq_len=MAX_SEQ)
        rr = [ref.submit(p, max_new_tokens=n) for p, n in work]
        ref.run()
        ref_wall = time.perf_counter() - t0
        outs = [r.result() for r in rr]
        if oracle is None:
            oracle = outs
        else:
            for a, b in zip(outs, oracle):
                assert a.shape == b.shape and (a == b).all()
            ref_tps = max(ref_tps, sum(
                o.size - p.size for o, (p, _) in zip(oracle, work))
                / ref_wall)

    def burst():
        eng = ServingEngine(model, max_batch=batch, max_seq_len=MAX_SEQ,
                            max_queue=max_queue)
        gw = ServingGateway(eng)
        clients = [GatewayClient("127.0.0.1", gw.port) for _ in work]
        results = [None] * offered      # (kind, payload, latency_s)
        barrier = threading.Barrier(offered + 1)

        def fire(i):
            prompt, new = work[i]
            barrier.wait()
            t = time.perf_counter()
            try:
                out = clients[i].generate(prompt, max_new_tokens=new,
                                          retries=0, timeout=120.0)
                results[i] = ("ok", out, time.perf_counter() - t)
            except EngineOverloaded as e:
                results[i] = ("shed", e.retry_after_ms,
                              time.perf_counter() - t)
            except BaseException as e:  # noqa: BLE001 — untyped = failure
                results[i] = ("error", type(e).__name__,
                              time.perf_counter() - t)

        threads = [threading.Thread(target=fire, args=(i,), daemon=True)
                   for i in range(offered)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join(300.0)
        wall = time.perf_counter() - t0
        info = eng.info()
        for c in clients:
            c.close()
        gw.stop(drain=True, timeout=30.0)
        return results, wall, info

    best = None                         # (goodput, results, info)
    shed_ms = []                        # shed latency pools across reps
    untyped = []
    mismatches = 0
    for _ in range(reps):
        results, wall, info = burst()
        accepted = [(i, r[1]) for i, r in enumerate(results)
                    if r and r[0] == "ok"]
        shed_ms += [r[2] * 1e3 for r in results if r and r[0] == "shed"]
        untyped += [r[1] for r in results if r and r[0] == "error"]
        mismatches += sum(1 for i, out in accepted
                          if out.shape != oracle[i].shape
                          or not (out == oracle[i]).all())
        acc_tokens = sum(out.size - work[i][0].size for i, out in accepted)
        goodput = acc_tokens / wall if wall > 0 else 0.0
        if best is None or goodput > best[0]:
            best = (goodput, results, info)
    goodput, results, info = best
    accepted = [(i, r[1]) for i, r in enumerate(results)
                if r and r[0] == "ok"]
    ratio = goodput / ref_tps if ref_tps else 0.0
    shed_p50, shed_p99 = _percentiles(shed_ms) if shed_ms else (0.0, 0.0)
    steps = [info["pressure"][f"level{i}_steps"] for i in range(4)]
    total_steps = max(1, sum(steps))

    payload = {
        "metric": "serving_overload_goodput_ratio",
        "value": round(ratio, 2),
        "unit": "x",
        # acceptance floor: goodput under 2x overload >= 0.8x closed-loop
        "vs_baseline": round(ratio / 0.8, 4),
        "backend": "cpu-proxy",
        "offered": offered,
        "reps": reps,
        # accepted/shed are the BEST rep's split (they sum to offered);
        # the shed-latency percentiles pool every rep's sheds
        "accepted": len(accepted),
        "shed": sum(1 for r in results if r and r[0] == "shed"),
        "untyped_errors": len(untyped),
        "max_batch": batch,
        "max_queue": max_queue,
        "shed_p50_ms": round(shed_p50, 2),
        "shed_p99_ms": round(shed_p99, 2),
        "accepted_tokens_per_sec": round(goodput, 1),
        "closed_loop_tokens_per_sec": round(ref_tps, 1),
        "token_mismatches": mismatches,
        "ladder_occupancy": {f"level{i}": round(s / total_steps, 3)
                             for i, s in enumerate(steps)},
    }
    print(json.dumps(payload), flush=True)

    _artifact(payload, {
        "workload": [{"prompt_len": int(p.size), "max_new": n}
                     for p, n in work],
        "engine_info": info,
        "untyped": untyped,
        "shed_latency_ms": shed_ms,
    })
    return payload


if __name__ == "__main__":
    if "--overload" in sys.argv[1:]:
        overload_main()
    elif "--shared-prefix" in sys.argv[1:]:
        shared_main()
    elif "--spec" in sys.argv[1:] or os.environ.get(
            "PT_SERVE_BENCH_SPEC", "0") not in ("0", ""):
        spec_main()
    else:
        main()
