"""modes `jamba-closed` and `jamba-open`: `modes/serve.py`'s run of a
ServingEngine (its set-up, `drive`, the load generator and `correct`: sampled
finished requests of the window judged on the reference's logits, the
lowering count) over a model of another family than the Llama decoder.

serve.py is wired to `model.build_model` and `reference.make_reference`, and
`drive` and `Cell.depth` read the traffic's `mode`; a configuration of
another family brings its builder and its reference as modules of their own
(`model_jamba.py`, `reference_jamba.py`) and this module runs serve.py with
those two in their place, on the cell with its mode's prefix put back to
`serve`.  Importing it fails at once on a program without
`paddle_tpu.models.jamba`.  It keeps in `engine_info` the engine's counters
that the cell's metrics read besides serve.py's.
"""
from __future__ import annotations

import dataclasses
from unittest import mock

import jax.numpy as jnp
import numpy as np
from paddle_tpu.models import jamba  # noqa: F401  (a parent without it: here)

from .. import model_jamba, reference_jamba
from . import serve

# Limits of `correct`, each from two readings on the v5e (my chip runs, PR 27;
# PERF.md, Findings): what this program reads over its seeds, and what it
# reads with the SSM state kept in bfloat16, the nearest precision below the
# configuration's (12% faster and 0.8 GB smaller, so worth guarding against).
#
# MARGIN, serve.py's check of the window's tokens on the reference's logits.
# serve.py's own 0.01 was measured on the Llama family (worst gap 0.35%); 28
# hybrid layers in bfloat16 are noisier (the logits' error is 0.4% of their
# range, rms): over 58 sampled requests the worst gap read 0.16-2.29%, mean
# 0.92% (five above 1.5%).  0.05 is 2.2 times the largest, the room STATE_TOL
# has; a wrong cache row, position or mask still misses by a large part of
# the range.  The tokens alone do NOT see the state's precision: a bfloat16
# state read 0.40-2.94% over 8 requests, which overlaps, so this limit is
# not the one that catches it.
MARGIN = 0.05
# STATE_TOL, so the state itself is compared: the first Mamba layer's SSM
# state of the two longest-running requests when the window closes (1,100 to
# 1,900 tokens in), against the reference's after the same tokens, as the
# norm of the difference over the norm.  This program: 0.12-0.36% (46
# requests).  A bfloat16 state: 1.77-2.36% (4 requests).  0.008 is their
# geometric middle: 2.2 times the largest of the one, the smallest of the
# other 2.2 times over it.
STATE_TOL = 0.008
# A traffic file's `tiny` block may give `margin` and `state_tol` of its own:
# at hidden 64 the rehearsal's bfloat16 noise is several times the real
# widths' (it read gaps to 2.3% and state errors to 0.44% on the CPU).
STATE_REQUESTS = 2
KEPT = ("prefill_positions", "prefill_positions_padded", "cache_bytes",
        "state_bytes_per_slot", "kv_bytes_per_position")
_serve_slim = serve._slim


def _slim(info: dict) -> dict:
    return {**_serve_slim(info), **{k: info[k] for k in KEPT}}


def _ssm_states(model, eng) -> list:
    """(tokens the state has seen, the first Mamba layer's SSM state
    [d_inner, n] as the engine holds it) of the longest-running requests."""
    from paddle_tpu.inference.serving import RequestState
    layer = next(i for i, l in enumerate(model.model.layers)
                 if not l.is_attention)
    running = sorted(
        ((slot, r) for slot, r in eng.scheduler.running().items()
         if r.state is RequestState.DECODING and len(r.output_tokens) > 1),
        key=lambda sr: -len(sr[1].output_tokens))[:STATE_REQUESTS]
    # between two steps the state has consumed all but the newest token
    return [(np.concatenate([r.prompt, r.output_tokens[:-1]]),
             np.asarray(eng._caches[layer][1][slot]).T)
            for slot, r in running]


def run(cell, env) -> dict:
    as_serve = dataclasses.replace(cell, traffic=dict(
        cell.traffic, mode="serve-" + cell.mode.split("-", 1)[1]))
    margin = float(cell.traffic.get("margin", MARGIN))
    state_tol = float(cell.traffic.get("state_tol", STATE_TOL))
    held, states = {}, []
    build, drive = serve.build, serve.drive

    def build_and_hold(cell_, env_):
        held["model"], held["eng"], rng = build(cell_, env_)
        return held["model"], held["eng"], rng

    def drive_then_read_states(eng, *args, **kw):
        out = drive(eng, *args, **kw)
        # the window is over and nothing steps the engine again; only host
        # copies are kept, so serve.run still frees the cache
        states.extend(_ssm_states(held.pop("model"), held.pop("eng")))
        return out

    with mock.patch.multiple(
            serve, bmodel=model_jamba, reference=reference_jamba, _slim=_slim,
            MARGIN=margin, build=build_and_hold,
            drive=drive_then_read_states):
        ev = serve.run(as_serve, env)
    # serve.run has dropped the model: the layers up to the first Mamba one
    # are made again from --seed (the same draws, a layer's worth)
    jcfg = model_jamba.jamba_config(cell.config, cell.depth())
    upto = 1 + next(i for i in range(cell.depth()) if not jcfg.is_attention(i))
    weights = model_jamba.weights_of(model_jamba.build_model(
        cell.config, upto, env.seed, jnp.bfloat16))
    first_state = reference_jamba.make_state_reference(cell.config)
    for ids, got in states:
        want = np.asarray(first_state(weights, upto, jnp.asarray(ids)))
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        ev["checks"].append((
            f"SSM state of the first Mamba layer after {ids.size} tokens "
            "against the reference's", {"relative_error": err}, state_tol,
            bool(np.isfinite(err) and err <= state_tol)))
    ev["checks"].append(("running requests whose state was compared",
                         len(states), STATE_REQUESTS,
                         len(states) == STATE_REQUESTS))
    return ev
