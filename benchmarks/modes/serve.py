"""modes `serve-open` and `serve-closed`: ServingEngine.submit + step() in
process (the gateway answers in one frame, so a first token does not exist
on a client's side of it; PERF.md, Open questions)."""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import harness, loadgen, model as bmodel, reference

# Tolerance of `correct`, with its reason.  The engine emits tokens, not
# logits, so each checked token is judged on the REFERENCE's logits at its
# position (teacher-forced on what the engine really generated, so an early
# near-tie does not cascade): the emitted token's reference logit must be the
# largest or within MARGIN of it, as a share of the row's logit range.
# Measured on the v5e over PR 23's runs of the two serving cells (92 sampled
# requests, 5,542 tokens, prompts to 2048): 97% of the tokens are the
# reference's argmax and the worst near-tie was 0.35% of the range.  MARGIN
# is three times that worst of thousands; a token read from a wrong cache
# row or position, a missing rotary offset or a mask off by one misses by a
# large part of the range.
MARGIN = 0.01
SPAN_RING = 262144


def build(cell, env):
    """The model with its seeded weights behind a ServingEngine, every
    shape of the cell's traffic warm: one prefill a bucket and the decode
    step, no other."""
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import ServingEngine

    tr, cfg = cell.traffic, cell.config
    model = bmodel.build_model(cfg, cell.depth(), env.seed, jnp.bfloat16)
    model.eval()
    env.phase("model and seeded weights")
    eng = ServingEngine(model, max_batch=int(tr["slots"]),
                        max_seq_len=int(tr["positions"]),
                        prefill_buckets=list(tr["prefill_buckets"]))
    rng = np.random.default_rng(env.seed)
    for b in eng.buckets:
        if b + 2 <= eng.max_seq_len:
            eng.submit(rng.integers(0, cfg["vocab_size"], b), max_new_tokens=2)
    eng.run()
    env.phase("engine built, buckets and decode step warm")
    return model, eng, rng


def drive(eng, cell, env, tr: dict, on_open=None) -> dict:
    """One lead-in and one window of `tr`'s traffic against the engine."""
    cfg = cell.config
    closed = tr["mode"] == "serve-closed"

    def submit(prompt, answer_len):
        return eng.submit(prompt, max_new_tokens=answer_len)

    t0 = time.perf_counter() + float(tr["lead_in_s"])
    if closed:
        first, pool = loadgen.closed_pool(tr, env.seed, cfg["vocab_size"])
        gen = loadgen.ClosedLoop(submit, first, pool)
    else:
        gen = loadgen.OpenLoop(submit, loadgen.open_schedule(
            tr, env.seconds, env.seed, cfg["vocab_size"]), t0)
    t1 = t0 + env.seconds
    t_give_up = t1 + float(tr["grace_s"])
    trace = harness.TraceSession(env.trace, env.out_dir, t1,
                                 float(tr["trace_seconds"]))
    before = after = info0 = info1 = None
    gen.start()
    try:
        while True:
            now = time.perf_counter()
            if before is None and now >= t0:
                before, info0 = env.compiles.snapshot(), eng.info()
                if on_open is not None:
                    on_open(t0)
            if after is None and now >= t1:
                after, info1 = env.compiles.snapshot(), eng.info()
                trace.mark_end()
            if now >= t1 and (now >= t_give_up or _all_started(
                    gen.records, t0, t1)):
                break
            trace.poll(now)
            gen.poll()
            with harness.annotate("bench.engine_step"):
                made = eng.step()
            if made == 0:
                with harness.annotate("bench.idle_wait"):
                    time.sleep(0.0005)
    finally:
        gen.stop()
        trace.stop()
    return {"t0": t0, "t1": t1, "records": list(gen.records), "trace": trace,
            "before": before, "after": after, "info0": info0, "info1": info1}


def run(cell, env) -> dict:
    import jax.numpy as jnp

    from paddle_tpu.observability import trace as ptrace

    tr, cfg, depth = cell.traffic, cell.config, cell.depth()
    closed = cell.mode == "serve-closed"
    spans_were_on = ptrace.enabled()
    if env.trace:
        # the program's own span ring, sized for a window's decode steps
        # (its default of 4096 records holds fewer)
        ptrace.enable(True)
        ptrace.set_ring_size(SPAN_RING)
    try:
        model, eng, rng = build(cell, env)
        d = drive(eng, cell, env, tr, on_open=env.window_opens)
    finally:
        ptrace.enable(spans_were_on)
    t0, t1, records, trace = d["t0"], d["t1"], d["records"], d["trace"]
    before, after, info0, info1 = (d["before"], d["after"], d["info0"],
                                   d["info1"])
    rows = [r.as_dict() for r in records]
    spans = [s for s in ptrace.trace_records()
             if s["name"] in ("engine.prefill", "engine.decode_step")]
    clock_skew_ns = time.monotonic_ns() - int(time.perf_counter() * 1e9)

    # -- correct: sampled finished requests of the window against the
    # reference's full forward
    if closed:
        counted = [r for r in records if r.sent is not None and r.sent < t1
                   and (r.req is None or not r.req.token_times
                        or r.req.token_times[-1] >= t0)]
    else:
        counted = [r for r in records if t0 <= r.due < t1]
    failed = [r for r in counted if r.error is not None or r.req is None
              or (r.req.error is not None)
              or (not closed and not r.req.token_times)]
    done = [r for r in counted if r.req is not None and r.req.done
            and r.req.error is None and r.req.output_tokens]
    pick = rng.permutation(len(done))[:int(tr["check_requests"])]
    samples = [(done[i].req.prompt.copy(),
                np.asarray(done[i].req.output_tokens)) for i in pick]
    weights = bmodel.weights_of(model)
    eng_buckets, positions = list(eng.buckets), eng.max_seq_len
    del eng, d, records
    gc.collect()        # the engine's KV cache goes before the reference runs

    checks = []
    ref_logits = reference.make_reference(cfg)
    k = int(tr["check_positions"])
    for prompt, out in samples:
        n = min(k, out.size)
        ids = np.zeros(positions, np.int64)
        seq = np.concatenate([prompt, out])
        ids[:seq.size - 1] = seq[:-1]
        pos = prompt.size - 1 + np.arange(out.size - n, out.size)
        lg = np.asarray(ref_logits(weights, depth, jnp.asarray(ids),
                                   jnp.asarray(pos)))
        top = lg.max(axis=1)
        gap = (top - lg[np.arange(n), out[-n:]]) / (top - lg.min(axis=1))
        checks.append((f"prompt {prompt.size} + {out.size} generated: last "
                       f"{n} tokens on the reference's logits",
                       {"worst_gap": float(gap.max()),
                        "exact": int((gap == 0).sum())}, MARGIN,
                       bool(np.isfinite(lg).all() and (gap <= MARGIN).all())))
    checks.append(("requests sampled for the reference", len(samples),
                   int(tr["check_requests"]),
                   len(samples) == int(tr["check_requests"])))
    delta = harness.counter_delta(before, after)
    delta["engine_step_lowerings"] = (info1["step"].get("lowerings", 0)
                                      - info0["step"].get("lowerings", 0))
    checks.append(("lowerings in the window", delta, 0,
                   not any(delta.values())))
    return {
        "t0": t0, "t1": t1, "requests": rows,
        "attempted": len(counted), "failed": len(failed),
        "checks": checks, "counters": delta, "trace": trace,
        "engine_info": {"before": _slim(info0), "after": _slim(info1)},
        "spans": spans, "clock_skew_ns": clock_skew_ns,
        "records": {"t0": t0, "t1": t1, "requests": rows,
                    "buckets": eng_buckets},
    }


def _all_started(records, t0, t1) -> bool:
    return all(r.error is not None or (r.req is not None
                                       and r.req.token_times)
               for r in records if t0 <= r.due < t1)


def _slim(info: dict) -> dict:
    keep = ("prefills", "decode_steps", "tokens_generated", "avg_occupancy",
            "submitted", "admitted", "finished", "timed_out", "evicted",
            "rejected", "queued", "active")
    return {k: info[k] for k in keep}
