"""The seam between the model zoo and the serving engine: a model says what
its steps compute, this module compiles, donates and keeps them.

What a model offers the serving engine (`inference/serving/engine.py`), and
all the engine knows of it:

- `config.max_position_embeddings`, the default extent of a slot;
- `parameters()`, handed to every step as its first runtime argument;
- `init_kv_caches(B, S)`: the per-slot state as any pytree of Tensors, the
  slot axis first in every leaf;
- `cache_kinds()`, optional: the kind of every leaf in the same structure,
  "kv" (grows a row a position), "state" (recurrent: a fixed cost a slot
  that cannot be rewound, shared by prefix or cut into chunks) or "window"
  (a sliding-window layer's ring of its last positions: a fixed cost a slot
  too, with the same three limits); `cache_kinds(model, caches)` below
  answers "kv" everywhere for a model that names none;
- `step_name`, the family's prefix of the compiled steps' names
  (`profiler.lint_summary()` and `tools/staticcheck` key on them);
- `slot_step_body(tok, caches, off, last_pos, return_logits=False)`: `tok`
  [B, S] and the caches as Tensors, `off` [B] each slot's position,
  `last_pos` [B] each row's last REAL token.  Returns `((next,), caches)`,
  or `((next, logits_row), caches)` with `return_logits`: the greedy next
  token [B] i32 by an argmax on the device, the [B, vocab] row it was taken
  from for the host's sampling, and the state after the window;
- `step_counters`, optional: `((name, entries), ...)` of what the slot step
  counts on the device.  Such a model's slot step returns, after its other
  outputs, one int32 vector of that layout; it comes back with the tokens,
  the engine adds the vectors up on the host and `info()` reports the sums
  by name (`models/mimo.py`: the routed experts' assignments);
- `verify_step_body(tok, caches, off)`, optional: `((argmax [B, W],), caches)`
  of a whole window at per-slot offsets.  A model without one cannot serve
  `spec_k` or `prefix_sharing`;
- `cached_step_body(tok, caches, off)`, optional and not the engine's:
  `generate()`'s step at ONE scalar offset, the oracle the engine's tokens
  are compared against.

`compiled_step(model, kind)` is the one accessor: engines, drafters and
`generate()` over the same weights get the same object, so they share its
lowerings.
"""
from __future__ import annotations

import jax

from ..autograd.grad_mode import no_grad
from ..core.tensor import Tensor
from ..jit import capture as _capture

# kind -> (the body it compiles, keyword arguments the body is bound to)
_KINDS = {"cached": ("cached", {}),
          "slot": ("slot", {}),
          "slot_logits": ("slot", {"return_logits": True}),
          "verify": ("verify", {})}


def build_step(model, kind: str):
    """A fresh compiled `step(params, tok, caches, *rest)` of `kind`:
    parameters are runtime arguments (a small HLO), the caches (argument 2)
    are donated so a decode updates them in place, and whole-step capture
    (`jit/capture.py`) memoizes one lowering per input signature; plain
    `jax.jit` when the capture tier is off."""
    base, bound = _KINDS[kind]
    body = getattr(model, f"{base}_step_body")
    plist = list(model.parameters())

    def step(param_vals, tok, caches, *rest):
        saved = [p._value for p in plist]
        try:
            for p, v in zip(plist, param_vals):
                p._value = v
            with no_grad():
                outs, new_caches = body(
                    Tensor(tok), jax.tree_util.tree_map(Tensor, caches),
                    *rest, **bound)
            return (*outs, jax.tree_util.tree_map(
                lambda t: t._value, new_caches))
        finally:
            # never leak tracers into the eager Parameters
            for p, v in zip(plist, saved):
                p._value = v

    step.__name__ = f"{model.step_name}_{base}_step"
    if _capture.step_capture_enabled():
        return _capture.capture_step(step, donate=(2,))
    return jax.jit(step, donate_argnums=(2,))


def compiled_step(model, kind: str):
    """THE step of `kind` over `model`'s weights, built on first need (an
    engine that never samples, shares a prefix or speculates never adds
    that step's lowerings) and kept on the model, with which it is
    collected."""
    steps = model.__dict__.setdefault("_compiled_steps", {})
    if kind not in steps:
        steps[kind] = build_step(model, kind)
    return steps[kind]


def cache_kinds(model, caches):
    """The kind of every leaf of `caches` (the model's `init_kv_caches`
    pytree), in its structure."""
    if hasattr(model, "cache_kinds"):
        return model.cache_kinds()
    return jax.tree_util.tree_map(lambda _: "kv", caches)
