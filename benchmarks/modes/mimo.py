"""mode `mimo-closed` (and `mimo-open`): `modes/serve.py`'s run of a
ServingEngine (its set-up, `drive`, the load generator and `correct`: sampled
finished requests of the window judged on the reference's logits, the
lowering count) over MiMo-V2-Flash: window and full attention over two kinds
of cache, routed experts of which this chip holds a share.

As `modes/jamba.py` does, it runs serve.py with the family's builder and
reference (`model_mimo.py`, `reference_mimo.py`) in the place of the Llama
ones, on the cell with its mode's prefix put back to `serve`; importing it
fails at once on a program without `paddle_tpu.models.mimo`.  It keeps in
`engine_info` the engine's counters that the cell's metrics read, and adds to
`correct` what the tokens cannot hold to a limit (top-8 of 256 flips on
rounding near ties and a window one position short moves a logit by little):

- the RING the engine holds when the window closes, all slots live: the
  first window layer's keys and values of the longest-running requests,
  against the reference's for the same tokens (`_rings`, `ring_check`);
- EVERY LAYER OF THE SERVED MODEL (the object the engine ran, not a copy), on
  tokens of a request the window served, at the timed widths, several slots
  at different lengths in one batch: its attention, a padded prefill a slot
  and then decode steps of all the slots together through the cache, and its
  routed experts' selections and output (`layer_checks`).
"""
from __future__ import annotations

import dataclasses
import types
from unittest import mock

import jax.numpy as jnp
import numpy as np
from paddle_tpu.models import mimo  # noqa: F401  (a parent without it: here)

from .. import model_mimo, reference_mimo
from . import serve

# Limits of `correct`, from readings on the v5e (my chip runs, PR 32: 27 runs
# of the cell, each with a seed of its own, the last 10 with the checks as
# they are now, and one run a variant with the PROGRAM altered and the
# reference not; the last three variants went through `benchmarks.run` at
# these limits and each read `correct: false`; PERF.md, Findings, has every
# number).
#
# MARGIN, serve.py's check of the window's tokens on the reference's logits
# (the emitted token's reference logit within MARGIN of the row's largest, as
# a share of the row's range).  This program: 54 sampled requests, 3,300
# tokens, 96% the reference's argmax, worst gaps 1.41%, 1.01%, 0.99%, 0.72%.
# The tokens do NOT tell the variants apart: a bfloat16 router read 0.11% and
# 0.14%, a window one position short 1.01%, which overlap the program's own;
# the sink left out of every window layer read 8.4% in one request of two.
# 0.03 lies between the program's worst (2.1 times over it) and the missing
# sink (2.8 times under it); a token read from a wrong cache row or ring
# row, a missing rotary offset or a mask off by many misses by a large part
# of the range.  The limits below are the ones the precision and the window
# are held by.
MARGIN = 0.03
# ATTN_TOL: the attention output (before the residual) of EVERY layer of the
# served model on the reference's own hidden states of a served request, a
# padded prefill a slot and then DECODE_STEPS decode steps of SLOTS slots
# together through the cache, against the reference's at the same
# positions; the largest error a position, as the norm of the difference
# over the norm.  This program: 0.37-0.56% in the prefill, 0.24-0.28%
# through a ring and 0.25-0.52% through a full layer's cache (17 readings of
# the first window layer alone, 10 of all seven layers).  A window one
# position short: 2.1-3.9% in the prefill of each of the five window layers
# (0.5-2.8% through their rings); the sink left out: 609-857% and 27-32% in
# each of them; V's scale left out: 42%.  0.01 is 1.8 times the largest of
# the one and the smallest prefill of the others 2.1 times over it.
ATTN_TOL = 0.01
# EXPERT_TOL: every expert layer's routed feed-forward on the reference's
# normed hidden states, handed to both in bfloat16: over the tokens whose 8th
# and 9th reference scores differ by more than SCORE_EPS (a tie closer than
# that may fall either way in any arithmetic), every selection must be the
# reference's, and this share's output must agree to EXPERT_TOL (norm of the
# difference over the norm, all those tokens together).  This program: every
# selection alike in every layer of every run (493-509 decided tokens of
# 512, 60 readings), output 0.383-0.396%.  A bfloat16 router in layers 2-6 only: 390-419 of 498-506
# selections alike in each of them (layer 1 untouched: 502 of 502), and the
# output 0.39% to 21% by whether a flipped selection was a held expert: the
# selections are what catches it; 0.02 is five times the program's output
# error and holds a wrong weight or a dropped assignment.
EXPERT_TOL = 0.02
# RING_TOL: the first window layer's ring as the ENGINE holds it when the
# window closes, all slots live: the keys and values of the RING_REQUESTS
# longest-running requests (488 to 5,338 tokens in) against the reference's
# for the same tokens, row block p % window holding position p; norm of the
# difference over the norm.  This program: 0.58-0.78% (26 readings: the
# hidden states are one bfloat16 layer deep).  The same rows against the
# reference one position on, what a write one row off would read, is made
# on every run beside it: 13-104%; a ring of 127 positions read 63-67%.
# 0.02 is 2.6 times the one, the smallest of the others 6.6 times over it.
RING_TOL = 0.02
SCORE_EPS = 1e-4
DECODE_STEPS = 8
LAYER_CHECK_POSITIONS = 512
SLOTS = 4
RING_REQUESTS = 2
KEPT = ("prefill_positions", "prefill_positions_padded", "cache_bytes",
        "kv_bytes_per_position", "window_bytes_per_slot", "moe_steps",
        "moe_assignments", "moe_assignments_local", "moe_experts_hit",
        "moe_expert_tokens")
_serve_slim = serve._slim


def _slim(info: dict) -> dict:
    return {**_serve_slim(info), **{k: info[k] for k in KEPT}}


def _rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _first_window_layer(cfg: dict) -> int:
    return next(i for i, w in enumerate(cfg["hybrid_layer_pattern"]) if w)


def _rings(model, eng) -> list:
    """(tokens the ring has seen, the first window layer's key rows and value
    rows [window x H_kv, lanes] as the engine holds them) of the
    longest-running requests, host copies."""
    from paddle_tpu.inference.serving import RequestState
    layer = next(i for i, l in enumerate(model.model.layers) if l.is_window)
    running = sorted(
        ((slot, r) for slot, r in eng.scheduler.running().items()
         if r.state is RequestState.DECODING and len(r.output_tokens) > 1),
        key=lambda sr: -len(sr[1].output_tokens))[:RING_REQUESTS]
    # between two steps the cache has consumed all but the newest token
    return [(np.concatenate([r.prompt, r.output_tokens[:-1]]),
             np.asarray(eng._caches[layer][0][slot], np.float32),
             np.asarray(eng._caches[layer][1][slot], np.float32))
            for slot, r in running]


def ring_check(cfg: dict, layers_of, weights, ids, k_rows, v_rows) -> dict:
    """What the engine's ring of the first window layer reads against the
    reference's keys and values of the same tokens: row block p % window
    must hold position p for the last `window` positions.  `ring`: the norm
    of the difference over the norm, keys and values together;
    `ring_rolled`: the same with the reference's rows one position on, what
    a write one row off would read (the second reading of RING_TOL, made on
    every run)."""
    i, w = _first_window_layer(cfg), int(cfg["sliding_window"])
    n = int(ids.size)
    padded = np.zeros(-(-n // reference_mimo.TRIM) * reference_mimo.TRIM,
                      np.int64)
    padded[:n] = ids
    ref = layers_of(weights, i + 1, jnp.asarray(padded))[i]
    pos = np.arange(max(n - w, 0), n)

    def rows(got, want, lanes, roll):
        # a ring of another extent than the configuration's is compared as
        # far as it goes, zeros beyond (it reads far off, and fails)
        hkv = want.shape[1]
        fit = np.zeros((w * hkv, got.shape[1]), np.float32)
        fit[:got.shape[0]] = got[:w * hkv]
        have = fit.reshape(w, hkv, -1)[(pos + roll) % w, :, :lanes]
        return have, np.asarray(want, np.float32)[pos]

    out = {}
    for name, roll in (("ring", 0), ("ring_rolled", 1)):
        pairs = [rows(k_rows, ref["k"], cfg["head_dim"], roll),
                 rows(v_rows, ref["v"], cfg["v_head_dim"], roll)]
        out[name] = _rel(np.concatenate([a.ravel() for a, _ in pairs]),
                         np.concatenate([b.ravel() for _, b in pairs]))
    return out


def layer_checks(model, cfg: dict, layers_of, experts_on, weights, ids,
                 positions: int) -> list:
    """The readings of every layer of `model` on `ids[:positions]`, one dict
    a layer: {"layer", "window", "attn_prefill", "attn_decode": largest
    relative error a position} and, for a layer with experts, {"decided":
    tokens whose 8th and 9th scores are apart; "selected_alike": those of
    them whose selection is the reference's; "expert": relative error of
    this share's output over them}.  Each layer is handed the REFERENCE's
    hidden states in its own type, so a layer's error is its own.  SLOTS
    slots share a batch at different lengths (each a sixteenth of the
    positions shorter than the one before, so no two write the same row of a
    ring): a padded prefill a slot into a cache of its own, the caches put
    side by side as the engine's slot write does, then DECODE_STEPS steps of
    all the slots together."""
    import paddle_tpu as P
    from paddle_tpu.models import experts

    n = int(positions)
    ids = np.asarray(ids)[:n]
    depth = len(model.model.layers)
    refs = layers_of(weights, depth, jnp.asarray(ids))
    bf16 = lambda a: P.to_tensor(jnp.asarray(a, jnp.bfloat16)[None])
    gap = (n - DECODE_STEPS) // (2 * SLOTS)
    reals = [n - DECODE_STEPS - gap * s for s in range(SLOTS)]
    out = []
    for i, (layer, ref) in enumerate(zip(model.model.layers, refs)):
        want = np.asarray(ref["attn"])
        size = np.linalg.norm(want, axis=1)
        worst = lambda got, at: float(
            (np.linalg.norm(got - want[at], axis=1) / size[at]).max())
        r = {"layer": i, "window": bool(layer.is_window)}
        with P.no_grad():
            normed = layer.input_layernorm(bf16(ref["x_in"]))
            slots, pre = [], 0.0
            for real in reals:
                got, cache = layer.self_attn(
                    normed, model.init_kv_caches(1, n)[i],
                    jnp.zeros((1,), jnp.int32), jnp.asarray([real], jnp.int32))
                pre = max(pre, worst(np.asarray(got._value[0, :real],
                                                np.float32), slice(0, real)))
                slots.append(cache)
            cache = tuple(P.to_tensor(jnp.concatenate(
                [c[j]._value for c in slots])) for j in (0, 1))
            dec = 0.0
            for t in range(DECODE_STEPS):
                at = np.asarray(reals) + t
                got, cache = layer.self_attn(
                    P.to_tensor(normed._value[0, at][:, None]), cache,
                    jnp.asarray(at, jnp.int32), jnp.ones((SLOTS,), jnp.int32))
                dec = max(dec, worst(np.asarray(got._value[:, 0],
                                                np.float32), at))
            r.update(attn_prefill=pre, attn_decode=dec)
            if layer.is_moe:
                # program and reference on the same inputs (the reference's,
                # in the program's type: a rounded input moves a score by
                # more than SCORE_EPS)
                ffn_in = bf16(ref["ffn_in"])
                on = experts_on(weights, i, ffn_in._value[0])
                y, _ = layer.mlp(ffn_in)
                opts = {k: layer.mlp.options[k] for k in (
                    "top_k", "norm_topk", "scale", "router_dtype")}
                sel, _ = experts.route(
                    ffn_in._value[0], layer.mlp.router_weight._value,
                    layer.mlp.router_bias._value, **opts)
                k = opts["top_k"]
                top = -np.sort(-np.asarray(on["biased"]), axis=1)[:, :k + 1]
                decided = (top[:, k - 1] - top[:, k]) > SCORE_EPS
                alike = (np.sort(np.asarray(sel), axis=1)
                         == np.sort(np.asarray(on["sel"]), axis=1)).all(axis=1)
                r.update(decided=int(decided.sum()),
                         selected_alike=int((alike & decided).sum()),
                         expert=_rel(np.asarray(y._value[0],
                                                np.float32)[decided],
                                     np.asarray(on["ffn"])[decided]))
        out.append(r)
    return out


def run(cell, env) -> dict:
    as_serve = dataclasses.replace(cell, traffic=dict(
        cell.traffic, mode="serve-" + cell.mode.split("-", 1)[1]))
    tr, cfg = cell.traffic, cell.config
    margin = float(tr.get("margin", MARGIN))
    attn_tol = float(tr.get("attn_tol", ATTN_TOL))
    expert_tol = float(tr.get("expert_tol", EXPERT_TOL))
    ring_tol = float(tr.get("ring_tol", RING_TOL))
    n_check = int(tr.get("layer_check_positions", LAYER_CHECK_POSITIONS))
    held, rings, seen = {}, [], []
    build, drive = serve.build, serve.drive

    def build_and_hold(cell_, env_):
        held["model"], held["eng"], rng = build(cell_, env_)
        return held["model"], held["eng"], rng

    def drive_then_read_rings(eng, *args, **kw):
        out = drive(eng, *args, **kw)
        # the window is over and nothing steps the engine again; only host
        # copies are kept, so serve.run still frees the cache (the model
        # stays: its layers are checked once the cache is gone)
        rings.extend(_rings(held["model"], held.pop("eng")))
        return out

    def make_reference(cfg_):
        logits = reference_mimo.make_reference(cfg_)

        def recording(weights, depth, ids, positions):
            seen.append(np.asarray(ids))
            return logits(weights, depth, ids, positions)
        return recording

    with mock.patch.multiple(
            serve, bmodel=model_mimo, _slim=_slim, MARGIN=margin,
            build=build_and_hold, drive=drive_then_read_rings,
            reference=types.SimpleNamespace(make_reference=make_reference)):
        ev = serve.run(as_serve, env)
    model = held.pop("model")
    weights = model_mimo.weights_of(model)
    layers_of, experts_on = reference_mimo.make_layer_reference(cfg)
    for ids, k_rows, v_rows in rings:
        r = ring_check(cfg, layers_of, weights, ids, k_rows, v_rows)
        ev["checks"].append((
            f"the engine's ring of the first window layer after {ids.size} "
            "tokens, all slots live, against the reference's keys and "
            "values (and against them one row on)", r, ring_tol,
            bool(r["ring"] <= ring_tol < r["ring_rolled"])))
    ev["checks"].append(("running requests whose ring was compared",
                         len(rings), RING_REQUESTS,
                         len(rings) == RING_REQUESTS))
    if seen:
        # of the sampled requests, the one with the most real tokens
        ids = max(seen, key=lambda a: int(np.flatnonzero(a)[-1]))
        for r in layer_checks(model, cfg, layers_of, experts_on, weights,
                              ids, n_check):
            what = (f"layer {r['layer']} of the served model "
                    f"({'window' if r['window'] else 'full'} attention) on "
                    f"{n_check} positions of a served request, {SLOTS} "
                    "slots: ")
            ev["checks"].append((
                what + "a padded prefill a slot, then decode through the "
                "cache, against the reference's",
                {k: r[k] for k in ("attn_prefill", "attn_decode")}, attn_tol,
                bool(max(r["attn_prefill"], r["attn_decode"]) <= attn_tol)))
            if "expert" in r:
                ev["checks"].append((
                    what + "its routed experts' selections where the 8th "
                    f"and 9th scores are more than {SCORE_EPS} apart, and "
                    "this share's output",
                    {k: r[k] for k in ("decided", "selected_alike",
                                       "expert")}, expert_tol,
                    bool(r["decided"] > 0
                         and r["selected_alike"] == r["decided"]
                         and r["expert"] <= expert_tol)))
    ev["checks"].append(("requests whose layers were compared",
                         min(len(seen), 1), 1, len(seen) >= 1))
    return ev
