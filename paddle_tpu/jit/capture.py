"""Whole-step program capture: trace once, optimize, lower once.

The tier above the per-op executable cache (ops/_op_cache.py). Eager
execution pays Python dispatch + tape bookkeeping + one XLA call PER OP
even when every op is served by a compiled executable; the reference's
L4/L5 layers (ProgramDesc -> PIR -> CINN) exist because whole-program
lowering is the next multiple. Here the pipeline is:

    record the step  ->  canonicalize to a graft program  ->  pass
    pipeline (fusion/cse/dve + donation inference, jit/passes/)  ->
    lower ONCE  ->  memoize by input avals

Recording reuses the existing machinery end to end: ops are jax functions,
so tracing the step replays the same dispatch path (`ops.dispatch.apply`)
the eager tier runs — `.backward()` walks the same GradNode tape, optimizer
updates run the same update rules — with tracer-valued Tensors. The
per-op cache sees the tracers and stands aside (counted as `captured`, see
`dispatch.cache_info()`), a dispatch-level recorder logs every op site into
the step's `GraftProgram` (static/graft_program.py), and `jax.make_jaxpr`
yields the canonical jaxpr the passes transform.

Tiering contract: **captured step -> per-op cache -> plain eager.** Any
capture bailout — a host sync inside the step (Tracer->numpy conversion,
data-dependent control flow), global-RNG draws that would bake randomness,
unhashable statics, a failing executable — poisons that signature and the
call (and all its successors) falls back to the eager path, where the
per-op cache serves individual ops exactly as before. Falling back is
always silent and value-correct; `capture_info()` says why it happened.

Entry points:
- ``capture_step(fn)`` / ``capture_step(donate="auto")(fn)`` — wrap an
  eager step function (Tensors/arrays in, Tensors/arrays out). One
  lowering per input-aval signature; LRU-bounded.
- ``lower_step(fn, example_args, ...)`` — one-signature lowering used by
  `parallel.trainer.TrainStep` and the `to_static` compile path: trace,
  run passes, return a jitted callable (falls back to ``jax.jit(fn)`` on
  any capture failure).

Env knobs:
- ``PT_STEP_CAPTURE`` (default 1) — 0 disables the tier everywhere (the
  per-op cache tier keeps working).
- ``PT_STEP_CAPTURE_SIZE`` (default 16) — signature-LRU bound per step.
- ``PT_STEP_CAPTURE_DONATE`` (default ``off``) — ``auto`` turns on
  donation inference for `capture_step` wrappers that don't choose.
- ``PT_STEP_CAPTURE_PASSES`` — see jit/passes/.
- ``PT_STEP_CAPTURE_LINT`` (default 1) — analyze-only jaxpr lint per
  lowering (jit/passes/lint.py); results in ``profiler.lint_summary()``.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Optional, Sequence

import jax
import jax.core as jcore
import jax.numpy as jnp
import numpy as np

from ..core import generator as gen
from ..core.tensor import Tensor
from ..observability import trace as _trace
from ..utils.memo import Lazy, LockedLRU
from . import passes as _passes
from .passes import lint as _lint
from .passes.donation import infer_donation

__all__ = ["capture_step", "CapturedStep", "lower_step", "capture_info",
           "capture_clear", "set_step_capture_enabled", "step_capture_enabled"]

_enabled = os.environ.get("PT_STEP_CAPTURE", "1").lower() not in ("0", "false")
_default_size = max(1, int(os.environ.get("PT_STEP_CAPTURE_SIZE", "16")))
_default_donate = os.environ.get("PT_STEP_CAPTURE_DONATE", "off").lower()


def set_step_capture_enabled(on: bool):
    global _enabled
    _enabled = bool(on)


def step_capture_enabled() -> bool:
    return _enabled


# ---------------------------------------------------------------------------
# global counters (profiler.step_capture_summary reads these)
# ---------------------------------------------------------------------------

class _Totals:
    __slots__ = ("lowerings", "hits", "bailouts", "fallback_calls",
                 "inlined_calls", "cse_folded", "consts_deduped",
                 "dve_removed", "donated_args", "last_bailout")

    def __init__(self):
        self.lowerings = 0       # capture->passes->jit pipelines completed
        self.hits = 0            # calls served by a lowered executable
        self.bailouts = 0        # captures abandoned (reason in last_bailout)
        self.fallback_calls = 0  # calls that ran the eager (per-op) tier
        self.inlined_calls = 0
        self.cse_folded = 0
        self.consts_deduped = 0
        self.dve_removed = 0
        self.donated_args = 0
        self.last_bailout = ""

    def snapshot(self) -> dict:
        return {s: getattr(self, s) for s in self.__slots__}


_TOTALS = _Totals()
_LOCK = threading.Lock()
_active = threading.local()   # re-entrancy guard: nested captures inline


def capture_info() -> dict:
    """Global capture-tier counters: lowerings/hits/bailouts + pass totals."""
    with _LOCK:
        return {"enabled": _enabled, **_TOTALS.snapshot()}


def capture_clear():
    """Reset the global counters (per-step caches live on their wrappers)."""
    with _LOCK:
        _TOTALS.__init__()


def _merge_report(report, donated=()):
    with _LOCK:
        _TOTALS.lowerings += 1
        _TOTALS.inlined_calls += report.inlined_calls
        _TOTALS.cse_folded += report.cse_folded
        _TOTALS.consts_deduped += report.consts_deduped
        _TOTALS.dve_removed += report.dve_removed
        _TOTALS.donated_args += len(donated)


def _lint_step(name: str, closed, report, donated=()):
    """Per-lowering jaxpr lint (passes/lint.py): analyze-only, recorded
    under the step's name for profiler.lint_summary()."""
    if not _lint.lint_enabled():
        return
    _lint.record_lint(name, closed, donated=donated,
                      comm_tagged=_lint.comm_tagged_of(report))


def _note_bailout(reason: str):
    with _LOCK:
        _TOTALS.bailouts += 1
        _TOTALS.last_bailout = reason[:200]


class _BailOut(Exception):
    """Capture abandoned; the caller falls back to the eager tier."""


# deferred imports, resolved once (the modules import ops.dispatch, which
# must finish importing first); memo.Lazy is the audited lazy-global idiom
def _import_call_deps():
    from ..amp.auto_cast import amp_cache_key
    from ..autograd.grad_mode import is_grad_enabled
    from ..ops import _op_cache, dispatch
    return amp_cache_key, is_grad_enabled, dispatch, _op_cache


_call_deps = Lazy(_import_call_deps)


# ---------------------------------------------------------------------------
# trace plumbing shared by capture_step and lower_step
# ---------------------------------------------------------------------------

def _is_tensor(x) -> bool:
    return isinstance(x, Tensor)


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


# captures are serialized process-wide: the dispatch recorder and the
# per-op cache's capturing flag are process-global hooks, so two threads
# capturing at once would interleave op records. Reentrant because a
# lower_step can run NESTED inside an outer capture (a to_static build
# inside a captured step) on the same thread.
_CAPTURE_LOCK = threading.RLock()


class _recording:
    """Install the dispatch op recorder + tell the per-op cache a capture
    is in flight; snapshot/restore global RNG so a (possibly failed) trace
    never perturbs the eager stream. The recorder callback is gated to the
    capturing thread, so a concurrent thread's eager ops never pollute
    this step's op record."""

    def __init__(self, op_names: list):
        self._ops = op_names

    def __enter__(self):
        from ..ops import _op_cache, dispatch
        _CAPTURE_LOCK.acquire()
        self._dispatch = dispatch
        self._op_cache = _op_cache
        # save/restore ALL capture state for the nested-capture case: the
        # inner exit must hand the outer trace its hooks back intact
        self._prev_cb = dispatch._capture_cb
        self._prev_capturing = _op_cache._capturing
        self._prev_active = getattr(_active, "on", False)
        tid = threading.get_ident()
        ops = self._ops

        def record(name, _tid=tid, _ops=ops):
            if threading.get_ident() == _tid:
                _ops.append(name)

        dispatch.set_capture_recorder(record)
        _op_cache.set_capturing(True)
        self._rng_state = gen.default_generator().get_state()
        _active.on = True
        return self

    def __exit__(self, *exc):
        _active.on = self._prev_active
        self._dispatch.set_capture_recorder(self._prev_cb)
        self._op_cache.set_capturing(self._prev_capturing)
        self._rng_after = gen.default_generator().get_state()
        gen.default_generator().set_state(self._rng_state)
        _CAPTURE_LOCK.release()
        return False

    def rng_drawn(self) -> bool:
        return self._rng_after["offset"] != self._rng_state["offset"]


def _amp_key():
    # amp.auto_cast.amp_cache_key — the one shared recipe for every
    # compile tier's amp-regime key component
    return _call_deps()[0]()


def _comms_key():
    # comms quant regime (distributed/comms): like amp, consulted at trace
    # time — a step captured exact must not serve quantized calls. False
    # (off) for the overwhelmingly common case; import stays lazy so the
    # capture tier never forces the distributed package in.
    try:
        from ..distributed.comms.api import comms_cache_key
        return comms_cache_key()
    except Exception:  # noqa: BLE001 — comms unavailable: one regime only
        return False


def _contains_tracer(leaves) -> bool:
    return any(isinstance(_unwrap(l), jcore.Tracer) for l in leaves)


# ---------------------------------------------------------------------------
# one-signature lowering (TrainStep / to_static integration)
# ---------------------------------------------------------------------------

_UNSET = object()


def _leaf_sig(v):
    shape = getattr(v, "shape", None)
    return (tuple(shape) if shape is not None else (),
            getattr(v, "dtype", None) or type(v),  # dtype OBJECT: str() is hot
            bool(getattr(v, "weak_type", False)))


def lower_step(fn: Callable, example_args: Sequence[Any],
               donate_argnums=(), in_shardings=_UNSET,
               out_shardings=_UNSET, passes=None,
               name: Optional[str] = None):
    """Trace ``fn`` once over concrete ``example_args``, run the graft pass
    pipeline, and return ``(dispatcher, GraftProgram | None)``.

    The dispatcher keeps ``fn``'s positional signature (so
    ``donate_argnums`` / ``in_shardings`` / ``.lower()`` keep their
    meaning) and serves the optimized executable for calls whose leaf
    avals match the example's; any OTHER signature (a smaller final batch,
    a dtype change) routes to a lazily-built plain ``jax.jit(fn, ...)``,
    which retraces per shape exactly like the pre-capture path. On ANY
    failure at lowering time — capture disabled, tracers in the examples,
    a trace error — the plain jit is all there is and the program is
    ``None``.
    """
    jit_kwargs: dict = {}
    if donate_argnums:
        jit_kwargs["donate_argnums"] = donate_argnums
    if in_shardings is not _UNSET:
        jit_kwargs["in_shardings"] = in_shardings
    if out_shardings is not _UNSET:
        # pin the output placements: a step whose body reshards (an
        # explicit shard_map exchange, a row-sharded table) must hand its
        # outputs back in the caller's canonical shardings, or the second
        # call's in_shardings reject the first call's outputs
        jit_kwargs["out_shardings"] = out_shardings
    if not _enabled:
        return jax.jit(fn, **jit_kwargs), None
    try:
        flat_example = jax.tree_util.tree_leaves(example_args)
        if _contains_tracer(flat_example):
            raise _BailOut("example args contain tracers")
        sig = tuple(_leaf_sig(v) for v in flat_example)
        step_name = name or getattr(fn, "__name__", "step")
        op_names: list = []
        with _trace.span("capture.trace", step=step_name):
            with _recording(op_names):
                closed, out_shape = jax.make_jaxpr(
                    fn, return_shape=True)(*example_args)
        out_def = jax.tree_util.tree_structure(out_shape)
        with _trace.span("capture.lower", step=step_name):
            closed, report = _passes.run_pipeline(closed, passes=passes)

        def _pt_captured_step(*args):
            flat = jax.tree_util.tree_leaves(args)
            out_flat = jcore.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
            return jax.tree_util.tree_unflatten(out_def, out_flat)

        jitted = jax.jit(_pt_captured_step, **jit_kwargs)
        # other-signature calls ride a plain jax.jit of the ORIGINAL fn —
        # built on first need, retraces per shape like the pre-capture path
        plain = Lazy(lambda: jax.jit(fn, **jit_kwargs))

        def dispatcher(*args):
            flat = jax.tree_util.tree_leaves(args)
            if tuple(_leaf_sig(v) for v in flat) == sig:
                with _trace.span("capture.execute", step=step_name):
                    return jitted(*args)
            with _LOCK:
                _TOTALS.fallback_calls += 1
            return plain()(*args)

        dispatcher.lower = jitted.lower
        # flat invar positions the jit donates (top-level argnums -> leaf
        # spans) — recorded on the program so the jaxpr lint's donation
        # rule sees what the executable actually aliases
        donated_flat: tuple = ()
        if donate_argnums:
            spans, start = [], 0
            for a in example_args:
                n = len(jax.tree_util.tree_leaves(a))
                spans.append((start, start + n))
                start += n
            wanted = set(donate_argnums)
            donated_flat = tuple(
                i for j, (lo, hi) in enumerate(spans) if j in wanted
                for i in range(lo, hi))
        from ..static.graft_program import GraftProgram
        prog = GraftProgram(
            closed, op_names, report,
            in_avals=tuple(v.aval for v in closed.jaxpr.invars),
            out_avals=tuple(getattr(v, "aval", None)
                            for v in closed.jaxpr.outvars),
            donate=donated_flat)
        _merge_report(report)
        # a caller-supplied name keeps lint records distinct when fn is a
        # wrapper lambda (the to_static path) — '<lambda>' rows would
        # clobber each other in profiler.lint_summary()
        _lint_step(step_name, closed, report, donated_flat)
        return dispatcher, prog
    except Exception as e:  # noqa: BLE001 — correctness net: plain jit
        _note_bailout(f"lower_step:{type(e).__name__}: {e}")
        return jax.jit(fn, **jit_kwargs), None


# ---------------------------------------------------------------------------
# capture_step: the aval-memoized eager-step tier
# ---------------------------------------------------------------------------

_EAGER = object()   # _call_captured's answer: serve this call eagerly


class _Entry:
    __slots__ = ("exec", "arr_pos", "out_def", "mask", "statics",
                 "program", "poisoned", "reason")

    def __init__(self):
        self.exec = None
        self.arr_pos = ()
        self.out_def = None
        self.mask = ()
        self.statics = ()
        self.program = None
        self.poisoned = False
        self.reason = ""


class CapturedStep:
    """A step function with whole-program capture per input-aval signature.

    Call it exactly like ``fn``. First call per signature captures +
    optimizes + lowers (exactly one compile); repeats run the executable;
    anything uncapturable runs ``fn`` eagerly, where the per-op cache tier
    applies. Outputs are detached (fresh Tensors): a captured step is a
    grad boundary, like TrainStep — do autograd INSIDE the step.
    """

    def __init__(self, fn: Callable, donate="default", maxsize=None,
                 allow_baked_rng: bool = False, passes=None):
        self._fn = fn
        self._donate = _default_donate if donate == "default" else donate
        self._allow_baked_rng = bool(allow_baked_rng)
        self._passes = passes
        self._cache = LockedLRU(maxsize=maxsize or _default_size)
        self._lock = threading.Lock()
        self.lowerings = 0
        self.hits = 0
        self.bailouts = 0
        self.fallback_calls = 0
        self.__name__ = getattr(fn, "__name__", "step")

    # ---- observability ----
    def cache_info(self) -> dict:
        return {"signatures": len(self._cache),
                "lowerings": self.lowerings, "hits": self.hits,
                "bailouts": self.bailouts,
                "fallback_calls": self.fallback_calls}

    def programs(self):
        """GraftPrograms of the currently-cached signatures."""
        return [e.program for _, e in self._cache.items()
                if e.program is not None]

    def bailout_reason(self) -> str:
        """Reason of the first poisoned signature, '' when none — the
        observability counterpart of cache_info()['bailouts'] (the
        staticcheck jaxpr tier reports it on a failed canonical step)."""
        for _, e in self._cache.items():
            if e.poisoned and e.reason:
                return e.reason
        return ""

    # ---- the tier ----
    def __call__(self, *args, **kwargs):
        _, is_grad_enabled, dispatch, _ = _call_deps()

        if not _enabled or getattr(_active, "on", False) \
                or dispatch._static_recorder is not None:
            # disabled / nested capture (ops inline into the outer trace) /
            # static mode: stay out of the way entirely
            return self._fn(*args, **kwargs)

        out = self._call_captured(args, kwargs, is_grad_enabled())
        if out is _EAGER:
            return self._fallback()(*args, **kwargs)
        return out

    def _call_captured(self, args, kwargs, grad_on):
        """The captured path of one call, or `_EAGER` where the eager tier
        has to serve it (unhashable or traced inputs, a poisoned
        signature, a capture or run failure)."""
        # capture.call times the tier's own call path (flatten, signature,
        # lookup, unflatten round capture.execute); a new signature shows
        # by name as its capture.trace / capture.lower children. It opens
        # inside this function, so that in a profiler's trace the span,
        # not the Python tracer's event of the function, is the innermost
        # name over the path. The eager tier runs after it has closed.
        with _trace.span("capture.call", step=self.__name__):
            leaves, treedef = jax.tree_util.tree_flatten(
                (args, kwargs), is_leaf=_is_tensor)
            sig = self._signature(leaves, treedef, grad_on)
            if sig is None:
                return _EAGER

            entry = self._cache.get(sig)
            if entry is not None and entry.poisoned:
                return _EAGER
            if entry is None:
                entry = _Entry()
                try:
                    self._capture(entry, leaves, treedef)
                except Exception as e:  # noqa: BLE001 — bailout: eager tier
                    entry.poisoned = True
                    entry.reason = f"{type(e).__name__}: {e}"[:200]
                    self._cache.put(sig, entry)
                    with self._lock:
                        self.bailouts += 1
                    _note_bailout(f"{self.__name__}:{entry.reason}")
                    return _EAGER
                self._cache.put(sig, entry)
                with self._lock:
                    self.lowerings += 1
            else:
                with self._lock:
                    self.hits += 1
                with _LOCK:
                    _TOTALS.hits += 1
            try:
                return self._run(entry, leaves)
            except Exception as e:  # noqa: BLE001 — poison + eager fallback
                entry.poisoned = True
                entry.reason = f"{type(e).__name__}: {e}"[:200]
                with self._lock:
                    self.bailouts += 1
                _note_bailout(f"{self.__name__}:run:{entry.reason}")
                # donation caveat: if the failed executable already consumed
                # a donated input buffer, rerunning eagerly on the same args
                # can only hit the same deleted array — raise the real story
                # instead of a confusing second failure
                if any(getattr(_unwrap(leaves[p]), "is_deleted", bool)()
                       for p in entry.arr_pos):
                    raise RuntimeError(
                        f"captured step {self.__name__!r} failed after "
                        f"donating an input buffer; the eager fallback "
                        f"cannot rerun on deleted arrays. Re-invoke with "
                        f"fresh inputs (the signature is poisoned and will "
                        f"run eagerly), or use donate='off'. Original failure: "
                        f"{entry.reason}") from e
                return _EAGER

    def _fallback(self):
        with self._lock:
            self.fallback_calls += 1
        with _LOCK:
            _TOTALS.fallback_calls += 1
        return self._fn

    def _signature(self, leaves, treedef, grad_on):
        _op_cache = _call_deps()[3]
        parts = []
        for l in leaves:
            v = _unwrap(l)
            if isinstance(v, jcore.Tracer):
                return None  # inside an enclosing trace: stay transparent
            if isinstance(v, (jax.Array, np.ndarray)):
                # the np.dtype OBJECT keys (hashable, value-equal): str() of
                # a dtype is measurably hot on the per-call signature path
                parts.append(("A", v.shape, v.dtype,
                              bool(getattr(v, "weak_type", False)),
                              isinstance(l, Tensor),
                              bool(l.stop_gradient)
                              if isinstance(l, Tensor) else True))
            else:
                f = _op_cache._freeze(v)
                if f is _op_cache._UNHASHABLE:
                    return None
                parts.append(("S", f))
        return (treedef, tuple(parts), bool(grad_on), _amp_key(),
                _comms_key())

    def _capture(self, entry: _Entry, leaves, treedef):
        fn = self._fn
        arr_pos = tuple(i for i, l in enumerate(leaves)
                        if isinstance(_unwrap(l), (jax.Array, np.ndarray)))
        entry.arr_pos = arr_pos
        out_info: dict = {}

        def flat_fn(*arrs):
            ll = list(leaves)
            for p, a in zip(arr_pos, arrs):
                orig = leaves[p]
                if isinstance(orig, Tensor):
                    t = Tensor(a, stop_gradient=orig.stop_gradient)
                    ll[p] = t
                else:
                    ll[p] = a
            a2, k2 = jax.tree_util.tree_unflatten(treedef, ll)
            out = fn(*a2, **k2)
            out_leaves, out_def = jax.tree_util.tree_flatten(
                out, is_leaf=_is_tensor)
            arrs_out, mask, statics = [], [], []
            for ol in out_leaves:
                v = _unwrap(ol)
                if isinstance(v, (jcore.Tracer, jax.Array)):
                    mask.append(isinstance(ol, Tensor))
                    statics.append(None)
                    arrs_out.append(v)
                else:
                    # trace-constant non-array output: baked per signature
                    mask.append(None)
                    statics.append(ol)
            out_info["out_def"] = out_def
            out_info["mask"] = tuple(mask)
            out_info["statics"] = tuple(statics)
            return tuple(arrs_out)

        op_names: list = []
        rec = _recording(op_names)
        with _trace.span("capture.trace", step=self.__name__):
            with rec:
                closed = jax.make_jaxpr(flat_fn)(
                    *(jnp.asarray(_unwrap(leaves[p])) for p in arr_pos))
        if rec.rng_drawn() and not self._allow_baked_rng:
            raise _BailOut(
                "step drew from the global RNG during capture; replays "
                "would reuse baked keys — pass the key as an argument or "
                "wrap with capture_step(allow_baked_rng=True)")

        with _trace.span("capture.lower", step=self.__name__):
            closed, report = _passes.run_pipeline(closed,
                                                  passes=self._passes)

        donated: tuple = ()
        if self._donate == "auto":
            donated = infer_donation(
                [v.aval for v in closed.jaxpr.invars],
                [getattr(v, "aval", None) for v in closed.jaxpr.outvars
                 if getattr(v, "aval", None) is not None])
        elif isinstance(self._donate, (tuple, list)):
            donated = self._donate_to_flat(leaves, treedef, arr_pos,
                                           self._donate)

        def _pt_captured(*arrs):
            return jcore.eval_jaxpr(closed.jaxpr, closed.consts, *arrs)

        _pt_captured.__name__ = f"ptcapture_{self.__name__}"
        entry.exec = jax.jit(_pt_captured, donate_argnums=donated)
        entry.out_def = out_info["out_def"]
        entry.mask = out_info["mask"]
        entry.statics = out_info["statics"]
        from ..static.graft_program import GraftProgram
        entry.program = GraftProgram(
            closed, op_names, report,
            in_avals=tuple(v.aval for v in closed.jaxpr.invars),
            out_avals=tuple(getattr(v, "aval", None)
                            for v in closed.jaxpr.outvars),
            donate=donated)
        report.donated_args = donated
        _merge_report(report, donated)
        _lint_step(self.__name__, closed, report, donated)

    @staticmethod
    def _donate_to_flat(leaves, treedef, arr_pos, donate_args):
        """Top-level positional-arg indices -> flat array positions."""
        args_kwargs = jax.tree_util.tree_unflatten(treedef, list(leaves))
        args = args_kwargs[0]
        spans, start = [], 0
        for a in args:
            n = len(jax.tree_util.tree_flatten(a, is_leaf=_is_tensor)[0])
            spans.append((start, start + n))
            start += n
        donate_set = set(donate_args)
        out = []
        for k, p in enumerate(arr_pos):
            for j, (lo, hi) in enumerate(spans):
                if lo <= p < hi and j in donate_set:
                    out.append(k)
                    break
        return tuple(out)

    def _run(self, entry: _Entry, leaves):
        with _trace.span("capture.execute", step=self.__name__):
            arrs = entry.exec(*(_unwrap(leaves[p]) for p in entry.arr_pos))
        it = iter(arrs)
        res = []
        for m, s in zip(entry.mask, entry.statics):
            if m is None:
                res.append(s)
            else:
                a = next(it)
                res.append(Tensor(a) if m else a)
        return jax.tree_util.tree_unflatten(entry.out_def, res)


def capture_step(fn: Optional[Callable] = None, *, donate="default",
                 maxsize: Optional[int] = None,
                 allow_baked_rng: bool = False, passes=None):
    """Wrap a whole train/decode step for capture-and-lower-once execution.

    ``donate``: ``"off"`` (no aliasing), ``"auto"`` (inference over
    input/output avals — see jit/passes/donation.py), or a tuple of
    top-level positional-arg indices whose buffers the caller will not
    reuse. Default comes from ``PT_STEP_CAPTURE_DONATE``.
    """
    def wrap(f):
        return CapturedStep(f, donate=donate, maxsize=maxsize,
                            allow_baked_rng=allow_baked_rng, passes=passes)
    if fn is not None:
        return wrap(fn)
    return wrap
