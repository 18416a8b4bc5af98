"""ServingEngine: continuous batching over the captured ragged decode path.

The inference loop the ROADMAP's "millions of users" direction asked for,
assembled from parts that already exist:

- the model's batch-slot step (`models/steps.py` holds what a model offers
  the engine and compiles it; the engine asks for a step by kind): per-slot
  position offsets feed the per-slot sequence-length vector of the ragged
  Pallas decode attention (`ops/pallas/decode_attention.py`), so every slot
  decodes at its own position inside ONE fixed-signature executable;
- whole-step capture (`jit/capture.py`): the decode step lowers once for
  the [max_batch, 1] signature and prefill lowers once per BUCKETED prompt
  length — steady-state serving never retraces (a capture bailout falls
  back to the per-op cache tier, slower but value-correct);
- the paged KV pool (`kv_pool.py`) + scheduler (`scheduler.py`): capacity-
  based admission, join/evict strictly between decode steps;
- typed deadlines (`utils/deadline.py`): per-request TTL -> RequestTimeout.

The per-slot state is the MODEL's pytree (`model.init_kv_caches`), every
leaf with the slot axis first: K and V `[B, S_max, H_kv, D]` a layer for the
Llama family; for a model with recurrent layers (`models/jamba.py`) K and V
for the layers that attend and a conv window and an SSM state for the
others, each leaf's kind named by the model ("kv" where it names none). The
slot write, the zero-maker and the step's donation work over leaves. Pages
count the positions of the "kv" leaves; recurrent state ("state") and a
sliding-window layer's ring of its last positions ("window",
`models/mimo.py`) are fixed costs a slot (`info()["state_bytes_per_slot"]`,
`info()["window_bytes_per_slot"]`) that cannot be shared by prefix, rewound
or cut into chunks, so over such a model `prefix_sharing`, `spec_k > 0` and
`prefill_chunk > 0` raise the typed FixedSlotStateUnsupported at
construction, and such a model's long prompts are never cut (below). A
model may count on the device (`step_counters`: the slot step returns one
int32 vector after its other outputs, which rides back with the tokens);
`info()` reports the sums by name.

Prefill/decode separation: a joining request's prompt is padded right to
the smallest configured bucket and prefilled alone at batch 1 (its last
REAL token's logits selected by a traced gather index, which is also the
length a recurrent layer stops its state at: right padding is safe under a
causal mask, not under a recurrence); the resulting state (KV rows, or a
layer's fixed state) is written into the request's batch slot by a donating
jitted copy.
Decode then serves every active slot per step. Slot rows are independent
across the batch in every op (rope, cache write, ragged attention, the
projections), so a join changes neither the tokens nor the lowering count
of in-flight requests — tests/test_serving.py asserts both, bitwise.

The prefill budget: WHILE ANY SLOT DECODES, ONE ENGINE STEP RUNS AT MOST
ONE PREFILL CALL, of at most C positions, so no gap between two of a
request's tokens holds more than one decode step and one such call. The
joined requests wait their turn in join order (FIFO); each step that
passes one over counts `info()["prefill_deferred"]`. A prompt longer than
C is CUT: one call a step of the captured slot step at `[1, C]` with
`off = [pos]` (it writes K/V at pos..pos+C and attends to the positions
up to its own, which is a piece of the prompt's prefill) over the
request's own `[1, S_max]` caches, each call launched and not waited on;
its last piece takes the smallest bucket that holds the remainder, reads
the first token and writes the slot, as a whole prefill does.
`info()["chunked_prefills"]` counts the prompts cut, `prefill_chunks` the
calls. C is the smallest configured bucket of at least 512 positions
(twice the v5e's FLOP-to-byte break-even, so a piece stays compute-bound
and cutting streams no more weight per useful FLOP; a bucket adds no
lowering), or ``PT_SERVE_PREFILL_CHUNK`` where that is set. Nothing is cut
where no bucket is that long or the model keeps "state" or "window"
leaves (a piece would have to carry them), nor, for the automatic C, when
no slot decodes: then every joiner prefills whole in the step it joins,
as before.

Speculative decoding (PT_SERVE_SPEC_K > 0): a drafter (speculative.py —
n-gram prompt-lookup by default, zero extra weights) proposes k tokens
per active slot and ONE captured [max_batch, k+1] verify call scores
every window position; the engine accepts the longest draft prefix
matching the target argmax plus the bonus token, so each verify emits
1..k+1 tokens per slot while the stream stays bitwise the greedy
non-speculative one. Rejection is cursor arithmetic — pages are reserved
for the whole lifetime (incl. the k-token verify scratch), so nothing
churns in the pool.

Env knobs (all read at engine construction):
- ``PT_SERVE_MAX_BATCH``   (default 8)   decode slots
- ``PT_SERVE_PAGE_SIZE``   (default 16)  tokens per KV page
- ``PT_SERVE_MAX_SEQ``     (default: model max_position_embeddings)
- ``PT_SERVE_PREFILL_BUCKETS`` comma list (default: powers of two)
- ``PT_SERVE_SPEC_K``      (default 0)   draft tokens per verify (0 = off)
- ``PT_SERVE_DRAFTER``     (default "ngram") ngram | model
- ``PT_SERVE_PREFILL_CHUNK`` (default 0 = C chosen from the buckets, as
  above) the cut length C, and a prompt longer than it is cut whether or
  not a slot decodes: pieces of [1, chunk] through the slot step, one a
  step while slots decode (at most ONE added lowering, none where the
  chunk is a bucket); a shared-prefix tail's windows are this long too
- ``PT_SERVE_PREFIX_SHARE`` (default 0 = off) radix-tree prefix sharing
  over committed KV pages: a request walks the tree, takes refs on the
  shared chain, and prefills only its O(suffix) tail (see prefix.py)
- ``PT_SERVE_MAX_QUEUE`` (default 8 x max_batch) bounded admission: a
  submit() past this queue depth is shed with the typed EngineOverloaded
  (terminal; carries retry_after_ms) instead of queueing unboundedly
- ``PT_SERVE_SHED_TTL`` (default 0 = off) enables deadline-aware
  shedding: when the projected queue wait (backlog tokens / measured
  token rate) exceeds a request's TTL (or this knob's value, for
  requests without one), submit() sheds it up front — the request would
  burn its whole deadline queued and time out anyway. Off by default so
  a TTL'd request queues to its own deadline unless the operator opts in

Overload control (the degradation ladder): under sustained queue pressure
the engine sheds OPTIONAL work in order — trim the prefix-sharing radix
tree (level 1), disable speculative decoding and return its verify-scratch
pages (level 2); level 3 sheds nothing more (the prefill budget already
holds a step to one prefill call while slots decode). Levels are
entered/exited with hysteresis (the exit
threshold sits a band below the enter threshold, so a queue oscillating on
a boundary cannot flap the ladder), every transition is stamped on the
trace ring, and the level + per-level step occupancy are exported as
gauges through the gateway's METRICS verb.
"""
from __future__ import annotations

import contextlib
import functools
import math
import os
import threading
import time
import weakref
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...distributed.chaos import faultpoint, register_fault
from ...core.tensor import Tensor
from ...models.steps import cache_kinds, compiled_step
from ...observability import trace
from ...utils.deadline import EngineOverloaded, env_int, env_timeout
from .kv_pool import KVPagePool
from .prefix import PrefixCache
from .request import Request, RequestState
from .scheduler import ContinuousBatchingScheduler
from .speculative import build_drafter

_ENGINES: "weakref.WeakSet[ServingEngine]" = weakref.WeakSet()

FP_PRESSURE = register_fault(
    "engine.pressure", "every engine step's overload-ladder evaluation "
    "passes here (the admission/degradation control point)")

# degradation-ladder hysteresis bands over queue_depth / max_queue: level
# L is entered at _LADDER_ENTER[L] and left below _LADDER_EXIT[L] — the
# gap is what keeps a queue oscillating on one boundary from flapping the
# ladder (each flap would churn the prefix tree / spec state for nothing)
_LADDER_ENTER = (0.0, 0.50, 0.75, 0.90)
_LADDER_EXIT = (0.0, 0.25, 0.50, 0.75)
_NO_SPAN = contextlib.nullcontext()   # reusable: an iteration not recorded


def _span(name: str, attrs):
    """A span whose correlation ids cost something to build (a rid list a
    decode step): `attrs` is called only when tracing is on — the near-zero
    off-cost law; off, this is the shared no-op like any other span."""
    if not trace.enabled():
        return trace.span(name)
    return trace.span(name, **attrs())


def _array(x):
    """The jax array of a step's output (the capture tier hands Tensors)."""
    return x._value if isinstance(x, Tensor) else x


def _launched(kind: str, out, attrs) -> None:
    """A call just launched, as a `device.<kind>` span once the device is
    seen done with it (observability/trace.py): `out` is an output no
    later call donates, and `attrs` is called only when tracing is on."""
    if trace.enabled():
        trace.launched("device." + kind, out, **attrs())


def _write_slot_impl(batch_caches, pref_caches, slot):
    """Donating slot write: a prefilled request's state (every leaf [1, ...]:
    KV rows, or a recurrent layer's fixed state) -> its batch row, leaf by
    leaf of the model's pytree."""
    z = jnp.asarray(0, jnp.int32)
    return jax.tree_util.tree_map(
        lambda b, p: jax.lax.dynamic_update_slice(
            b, p.astype(b.dtype), (slot,) + (z,) * (b.ndim - 1)),
        batch_caches, pref_caches)


# ONE jitted writer process-wide (it closes over nothing): jax.jit memoizes
# per cache-shape signature, so every engine over a given layout shares one
# compile instead of paying a fresh ~50ms lowering per ServingEngine — the
# difference between a TTFT and a compile benchmark for short-lived engines
_write_slot = jax.jit(_write_slot_impl, donate_argnums=(0,))


def _write_scratch_impl(batch_caches, scratch_caches, slot):
    """Slot write for the scratch-prefill path: the per-request scratch is
    [1, S_max + W] (window writes may legally spill past S_max into the
    pad, so dynamic_update_slice never clamps a chunk into valid rows);
    only the [0, S_max) prefix lands in the batch row."""
    z = jnp.asarray(0, jnp.int32)
    out = []
    for (bk, bv), (sk, sv) in zip(batch_caches, scratch_caches):
        s_max = bk.shape[1]
        out.append(
            (jax.lax.dynamic_update_slice(
                bk, sk[:, :s_max].astype(bk.dtype), (slot, z, z, z)),
             jax.lax.dynamic_update_slice(
                 bv, sv[:, :s_max].astype(bv.dtype), (slot, z, z, z))))
    return out


_write_scratch = jax.jit(_write_scratch_impl, donate_argnums=(0,))


def _zero_caches_impl(treedef, shapes, dtypes):
    """A prefill's scratch state: fresh zero buffers, each its own (the slot
    step donates every one of them), in the structure of the model's pytree
    with a shape and a dtype a leaf."""
    return treedef.unflatten(
        [jnp.zeros(s, d) for s, d in zip(shapes, dtypes)])


# ONE jitted maker process-wide, like the writers above: every argument is
# static, so a prefill pays one dispatch for all its buffers instead of one
# eager jnp.zeros each (0.65 ms apiece on the chip whatever the size), and
# the signature does not depend on the prefill's bucket
_zero_caches = jax.jit(_zero_caches_impl, static_argnums=(0, 1, 2))


def _merge_tokens_impl(from_host, host_tok, prev_nxt):
    """The next decode step's input tokens without a trip to the host: the
    host's value where it has one (a prefill's first token, 0 for an idle
    slot), the token the step in flight computed for the slot elsewhere."""
    return jnp.where(from_host, host_tok,
                     prev_nxt.astype(host_tok.dtype)).reshape(-1, 1)


@functools.lru_cache(maxsize=None)
def _merge_tokens(max_batch: int, tok_dtype):
    """The merge compiled ahead of time for `max_batch` slots, one
    executable process-wide a batch width. Compiled here and not by
    `jax.jit` at its first call: a step's output and a fresh constant differ
    in whether they are committed to a device, which `jit` keys a lowering
    on, and the second kind would be met first in the middle of serving."""
    return jax.jit(_merge_tokens_impl).lower(
        jax.ShapeDtypeStruct((max_batch,), jnp.bool_),
        jax.ShapeDtypeStruct((max_batch,), tok_dtype),
        jax.ShapeDtypeStruct((max_batch,), jnp.int32)).compile()


class _Flight:
    """A decode step that was launched and whose tokens the host has not
    read: which request each of its rows was computed for (a row is dropped
    at collect where the request ended meanwhile) and the device's outputs,
    their download to the host already started."""

    __slots__ = ("index", "rows", "by_slot", "nxt", "logits", "counted")

    def __init__(self, index, rows, nxt, logits, counted):
        self.index, self.rows, self.by_slot = index, rows, dict(rows)
        self.nxt, self.logits, self.counted = (
            _array(x) for x in (nxt, logits, counted))
        for out in (self.nxt, self.logits, self.counted):
            start = getattr(out, "copy_to_host_async", None)
            if start is not None:
                start()

    def holds(self, slot: int, req: Request) -> bool:
        """Whether the row of `slot` was computed for `req`."""
        return self.by_slot.get(slot) is req


# why a decode step was launched with nothing in flight, `info()
# ["decode_ahead"]["settled"]`'s keys
SETTLE_CAUSES = ("prefill", "sampling", "speculative", "outside_read",
                 "empty")


class SamplingUnsupported(NotImplementedError):
    """A submit() asked for sampling this engine cannot honor; rejected up
    front with this typed error instead of silently decoding greedy.

    Non-speculative engines DO serve per-slot temperature sampling now
    (host-side off the returned logits row; optional top_p nucleus on
    top), so this fires only for (a) any non-greedy ask on a SPECULATIVE
    engine — greedy acceptance is what makes the speculative stream exact,
    so spec engines stay greedy-only — and (b) top_p < 1 without a
    positive temperature, which has no sampling distribution to draw
    from. `temperature=0` / `top_p=1` are exactly greedy and always
    accepted."""

    def __init__(self, param: str, value, why: str = ""):
        self.param = param
        self.value = value
        why = why or ("this engine decodes greedily (deterministic argmax "
                      "per slot) for this parameter combination")
        super().__init__(
            f"{param}={value!r} cannot be honored: {why}. Pass {param}="
            f"{'0' if param == 'temperature' else '1'} (or omit it) for "
            f"greedy decoding.")


class FixedSlotStateUnsupported(NotImplementedError):
    """An engine option that shares, rewinds or cuts up K/V pages was asked
    of a model part of whose per-slot state is a fixed cost a slot and no
    row a position: recurrent state (a conv window, an SSM state: `kind`
    "state"), a function of the WHOLE prefix, or a sliding-window layer's
    ring of its last positions ("window"), which has forgotten the prefix.
    A prefix's pages cannot stand for either (prefix sharing), a rejected
    draft cannot be taken back out of them (speculation: the ring has
    overwritten what the draft displaced), and a prompt cut into pieces
    would have to carry them from piece to piece (chunked prefill).
    Refused at construction, never served wrong."""

    _WHAT = {"state": "recurrent state",
             "window": "a sliding window's ring of its last positions"}

    def __init__(self, param: str, value, kind: str = "state"):
        self.param = param
        self.value = value
        self.kind = kind
        super().__init__(
            f"{param}={value!r} cannot be honored: the model keeps "
            f"{self._WHAT.get(kind, kind)} beside its K/V cache, which this "
            f"option cannot share, rewind or carry across windows. Leave "
            f"{param} off.")


# kinds of cache leaf (`models/steps.py cache_kinds`): "kv" grows a row a
# position and is what pages count; the others are fixed costs a slot
CACHE_KINDS = ("kv", "state", "window")

# the shortest automatic cut: twice the v5e's FLOP-to-byte break-even
# (197e12 / 819e9 ~ 240 positions), so a piece of a cut prompt stays
# compute-bound (the module docstring's prefill budget)
_CUT_MIN = 512


def _normalize_buckets(vals, max_seq_len: int) -> List[int]:
    """One bucket policy for both knob paths: clamp every bucket to the
    static cache extent (a bucket past S_max would trace a KV write larger
    than the cache), dedupe/sort, and terminate the ladder at max_seq_len
    so every admissible prompt has a bucket."""
    out = sorted({min(int(b), max_seq_len) for b in vals if int(b) > 0})
    if not out or out[-1] < max_seq_len:
        out.append(max_seq_len)
    return out


def _default_buckets(max_seq_len: int) -> List[int]:
    # unparseable env tokens degrade to the default ladder (same contract
    # as env_timeout/env_int: a typo'd knob must not kill serving)
    vals = []
    for tok in os.environ.get("PT_SERVE_PREFILL_BUCKETS", "").split(","):
        try:
            vals.append(int(tok))
        except ValueError:
            continue
    if not any(b > 0 for b in vals):
        vals, b = [], 8
        while b < max_seq_len:
            vals.append(b)
            b *= 2
    return _normalize_buckets(vals, max_seq_len)


class ServingEngine:
    """Continuous-batching generation over one model's weights.

    Greedy decoding (the deterministic contract the join/evict bitwise
    tests rely on); temperature sampling is a recorded follow-on. Thread
    safety: `submit()` may be called from any thread; `step()`/`run()`
    must be driven by one thread (the engine serializes them with a lock,
    matching the Predictor.clone() multi-thread serving contract where
    compute stays single-driver per engine).

    A step in flight. A greedy decode step is launched ONE AHEAD: `step()`
    launches step i+1 and only then reads step i's tokens (whose download
    began when step i was launched), so the scheduler pass, the prep, the
    capture tier's call, the emit loop and the driver's own work between two
    calls all run while the device computes. Step i+1 takes a continuing
    slot's token from step i's output on the device (`_merge_tokens`); who
    is in it is decided without step i's tokens: a request whose token in
    flight is its last by `max_new_tokens` is left out, and one that ends
    in a way the host could not foresee (EOS, a TTL eviction) has one row
    computed for nothing, dropped when that step is read (what the row
    wrote lies in a slot the next prefill's slot write, later in the
    device's order, overwrites whole). What cannot run ahead does not, by
    what the engine sees in its input and no knob: a step with a sampled
    slot (the host draws from the logits row), an engine with a drafter
    (it needs the emitted tokens) and a shared-prefix tail's scratch window
    read the step in flight first. A bucketed prefill, or a piece of a cut
    prompt, computes on buffers of its own (only its slot write, queued
    behind, touches the batch's), so it is launched with the step in
    flight; a call whose first token the host reads (a whole prefill, a
    cut prompt's last piece) then reads that step before its own token,
    so the step's tokens do not wait out the call and no gap between two
    of a request's tokens holds two prefill calls.

    The contract that goes with it: BETWEEN TWO CALLS A STEP MAY BE IN
    FLIGHT; EVERY READ FROM OUTSIDE SEES THE ENGINE AS IF IT WERE NOT.
    `settle()` reads the step in flight, emits its tokens and launches
    nothing; `scheduler`, `_caches` and `info()` settle before they answer,
    and `run()` / `generate()` return settled. So after any number of
    `step()` calls, `eng.scheduler.running()` and then the caches read a
    state that has consumed all but each request's newest token, as they
    did when a step was read before `step()` returned. The engine's own
    loop goes by `_scheduler` and `_slot_caches`, which do not settle; a
    driver asks `idle`, `queue_depth` and `active`, bookkeeping a step in
    flight cannot change. A Request's `output_tokens` hold what was emitted
    so far, at most one token a request behind the device.
    `info()["decode_ahead"]` counts it: `launched_ahead` of `decode_steps`,
    `settled` by the cause that left nothing in flight (SETTLE_CAUSES),
    `rows_dropped`.
    """

    def __init__(self, model, max_batch: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 eos_token_id: Optional[int] = None,
                 default_ttl: Optional[float] = None,
                 spec_k: Optional[int] = None,
                 drafter=None, draft_model=None,
                 prefill_chunk: Optional[int] = None,
                 prefix_sharing: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 shed_ttl: Optional[float] = None):
        self.model = model
        cfg = model.config
        self.max_batch = max_batch or env_int("PT_SERVE_MAX_BATCH", 8)
        self.max_seq_len = max_seq_len or env_int(
            "PT_SERVE_MAX_SEQ", cfg.max_position_embeddings)
        self.eos_token_id = eos_token_id
        self.default_ttl = default_ttl
        self.spec_k = env_int("PT_SERVE_SPEC_K", 0) if spec_k is None \
            else int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k and self.spec_k + 1 >= self.max_seq_len:
            raise ValueError(
                f"spec_k={self.spec_k} leaves no room for prompts in "
                f"max_seq_len={self.max_seq_len}")
        page = page_size or env_int("PT_SERVE_PAGE_SIZE", 16)
        pages_per_slot = -(-self.max_seq_len // page)
        # the cut length, where it is given (0: chosen from the buckets
        # below; the module docstring's prefill budget)
        self.prefill_chunk = env_int("PT_SERVE_PREFILL_CHUNK", 0) \
            if prefill_chunk is None else int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}")
        # prefix sharing: radix-tree index over committed KV pages
        if prefix_sharing is None:
            prefix_sharing = os.environ.get(
                "PT_SERVE_PREFIX_SHARE", "0").strip().lower() not in (
                "0", "", "false", "off")
        # the per-slot state, as the model's own pytree (every leaf has the
        # slot axis first)
        self._slot_caches = jax.tree_util.tree_map(
            lambda t: t._value,
            model.init_kv_caches(self.max_batch, self.max_seq_len),
            is_leaf=lambda t: isinstance(t, Tensor))
        leaves, treedef = jax.tree_util.tree_flatten(self._slot_caches)
        kinds = jax.tree_util.tree_leaves(cache_kinds(model, self._slot_caches))
        unknown = set(kinds) - set(CACHE_KINDS)
        if unknown:
            raise ValueError(f"cache_kinds() names {sorted(unknown)}; the "
                             f"engine knows {CACHE_KINDS}")
        self._cache_bytes = {
            kind: sum(a.nbytes for a, k in zip(leaves, kinds) if k == kind)
            for kind in CACHE_KINDS}
        for kind in CACHE_KINDS[1:]:
            for param, value in (("spec_k", self.spec_k),
                                 ("prefill_chunk", self.prefill_chunk),
                                 ("prefix_sharing", bool(prefix_sharing))):
                if value and self._cache_bytes[kind]:
                    raise FixedSlotStateUnsupported(param, value, kind)
        self._zero_args = (treedef,
                           tuple((1,) + a.shape[1:] for a in leaves),
                           tuple(a.dtype for a in leaves))
        # (S_max, Hkv, D) of every K and V: the scratch-prefill path (K/V
        # only by nature) assembles host copies of this shape
        self._cache_shape = leaves[0].shape[1:]
        self._cache_dtype = leaves[0].dtype
        # pages count the "kv" leaves' positions; a recurrent layer's state
        # and a window layer's ring are fixed costs a slot, which the pool
        # reports beside them
        self.pool = KVPagePool(
            self.max_batch * pages_per_slot, page,
            page_bytes=page * self.kv_bytes_per_position,
            slot_state_bytes=self.state_bytes_per_slot,
            slot_window_bytes=self.window_bytes_per_slot)
        self.prefix_cache = PrefixCache(self.pool) if prefix_sharing \
            else None
        # speculative slots reserve k extra positions of verify scratch:
        # a verify window may write k tokens past the accepted cursor, and
        # those positions must be capacity the request already owns
        self._scheduler = ContinuousBatchingScheduler(
            self.pool, self.max_batch, reserve_extra_tokens=self.spec_k)
        if self.prefix_cache is not None:
            # admission pressure evicts tree-only pages instead of wedging
            self._scheduler.reclaim = self.prefix_cache.evict
        # the window of the scratch path, which serves O(suffix) tails
        # after a prefix share and nothing else
        self._window = self.prefill_chunk or page
        self._scratch_len = self.max_seq_len + self._window
        self._window_fn = None
        if prefill_buckets:
            if not any(int(b) > 0 for b in prefill_buckets):
                raise ValueError(
                    f"prefill_buckets {list(prefill_buckets)!r} has no "
                    f"positive entry")
            self.buckets = _normalize_buckets(prefill_buckets,
                                              self.max_seq_len)
        else:
            self.buckets = _default_buckets(self.max_seq_len)
        # C, the longest prefill call a step runs while slots decode (0:
        # prompts are not cut); the joined requests not yet decoding, in
        # join order
        fixed = any(self._cache_bytes[k] for k in CACHE_KINDS[1:])
        self._cut = self.prefill_chunk or next(
            (b for b in self.buckets if b >= _CUT_MIN and not fixed), 0)
        self._prefilling: List[Request] = []

        self._params = [p._value for p in model.parameters()]
        # constant operands of the slot step (never donated: only the caches
        # are), made once instead of one eager dispatch a call
        self._prefill_off = jnp.zeros((1,), jnp.int32)
        self._piece_last = jnp.asarray([self._cut - 1], jnp.int32)
        self._decode_last_pos = jnp.zeros((self.max_batch,), jnp.int32)
        self._step_fn = compiled_step(model, "slot")
        # the decode step in flight (None: the engine is settled), the merge
        # that feeds the next one from it, and what stands for its tokens
        # when nothing is in flight
        self._flight: Optional[_Flight] = None
        self._tok_dtype = jnp.asarray(np.zeros((), np.int64)).dtype
        self._merge = _merge_tokens(self.max_batch, self._tok_dtype)
        self._no_tokens = jnp.zeros((self.max_batch,), jnp.int32)
        # why nothing is in flight, until the next launch counts it
        self._line = "empty"
        self._launched = 0
        self._ahead = {"launched_ahead": 0, "rows_dropped": 0,
                       "settled": dict.fromkeys(SETTLE_CAUSES, 0)}
        # what the model's slot step counts on the device: (name, entries)
        # of the int32 vector it returns after its other outputs, summed
        # here on the host as the vectors come back with the tokens
        self._step_counters = tuple(getattr(model, "step_counters", ()))
        self._counted = np.zeros(
            sum(n for _, n in self._step_counters), np.int64)
        self._verify_fn = None
        self.drafter = None
        if self.spec_k:
            self._verify_fn = compiled_step(model, "verify")
            self.drafter = build_drafter(
                drafter or os.environ.get("PT_SERVE_DRAFTER", "ngram"),
                self.max_batch, self.max_seq_len, draft_model=draft_model)

        # bounded admission (the overload front door): a queue past
        # max_queue — or a projected wait past the TTL — sheds at submit
        self.max_queue = env_int("PT_SERVE_MAX_QUEUE", 8 * self.max_batch) \
            if max_queue is None else int(max_queue)
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        self.shed_ttl = env_timeout("PT_SERVE_SHED_TTL", 0.0) \
            if shed_ttl is None else float(shed_ttl)
        # degradation ladder state (driven by _update_pressure each step)
        self._pressure = 0
        self._level_steps = [0, 0, 0, 0]
        self._spec_paused = False
        self._prefix_paused = False

        self._lock = threading.Lock()   # serializes step()/run()
        self._counters = {"prefills": 0, "decode_steps": 0,
                          "tokens_generated": 0, "rejected": 0,
                          "verify_steps": 0, "draft_tokens_proposed": 0,
                          "draft_tokens_accepted": 0, "sampled_tokens": 0,
                          "prefill_chunks": 0, "chunked_prefills": 0,
                          "prefill_deferred": 0,
                          "shared_prefix_joins": 0, "prefill_pages_saved": 0,
                          "shed": 0, "pressure_trims": 0, "spec_pauses": 0,
                          "scratch_pages_returned": 0,
                          "prefill_positions": 0,
                          "prefill_positions_padded": 0}
        # tokens-per-verify histogram: index i = verifies that emitted i
        # tokens for a slot (1..k+1)
        self._accept_hist = [0] * (self.spec_k + 2)
        self._occupancy_sum = 0.0
        self._decode_time = 0.0
        self._prefill_time = 0.0
        _ENGINES.add(self)

    # ------------------------------------------------------------------
    # intake
    # ------------------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 16,
               ttl: Optional[float] = None,
               eos_token_id: Optional[int] = None,
               temperature: Optional[float] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None) -> Request:
        """Enqueue one request; returns the live Request handle. Raises a
        typed ValueError immediately when the request can NEVER fit the
        engine's static cache layout (that is a sizing bug, not load), and
        the typed SamplingUnsupported for sampling asks the engine cannot
        honor (never silently greedy): non-speculative engines serve
        temperature (+ optional top_p nucleus) per slot, host-side;
        speculative engines are greedy-only by construction. ``seed``
        makes a sampled request's stream reproducible (default: its rid)."""
        if temperature is not None and not (
                math.isfinite(float(temperature)) and float(temperature) >= 0.0):
            with self._lock:
                self._counters["rejected"] += 1
            raise SamplingUnsupported(
                "temperature", temperature, why="temperature must be a "
                "finite value >= 0 (a negative temperature would invert "
                "the distribution, which no engine serves)")
        if top_p is not None and not (
                math.isfinite(float(top_p)) and 0.0 < float(top_p) <= 1.0):
            with self._lock:
                self._counters["rejected"] += 1
            raise SamplingUnsupported(
                "top_p", top_p, why="top_p must lie in (0, 1] — the "
                "nucleus is the smallest prefix of the sorted distribution "
                "reaching top_p, which is empty at <= 0 and over-full "
                "past 1")
        greedy_t = temperature is None or float(temperature) == 0.0
        greedy_p = top_p is None or float(top_p) == 1.0
        if greedy_t and not greedy_p:
            # checked BEFORE the speculative branch: top_p-sans-temperature
            # is rejected by EVERY engine, so "submit to a non-speculative
            # engine" would be wrong guidance for this ask
            with self._lock:
                self._counters["rejected"] += 1
            raise SamplingUnsupported(
                "top_p", top_p, why="top_p nucleus filtering needs a "
                "positive temperature to define the sampling distribution "
                "(temperature-only or temperature+top_p are served)")
        if self.spec_k and not (greedy_t and greedy_p):
            # greedy acceptance is the exactness argument; a sampled slot
            # inside a speculative batch would need lossy acceptance rules
            param, val = (("temperature", temperature) if not greedy_t
                          else ("top_p", top_p))
            with self._lock:
                self._counters["rejected"] += 1
            raise SamplingUnsupported(
                param, val, why="this engine decodes SPECULATIVELY "
                "(spec_k={}) and greedy verification is what keeps the "
                "speculative stream exact — submit to a non-speculative "
                "engine for per-slot sampling".format(self.spec_k))
        req = Request(prompt_ids, max_new_tokens=max_new_tokens,
                      ttl=self.default_ttl if ttl is None else ttl,
                      eos_token_id=self.eos_token_id
                      if eos_token_id is None else eos_token_id,
                      temperature=None if greedy_t else float(temperature),
                      top_p=None if greedy_p else float(top_p),
                      seed=seed)
        total = req.prompt.size + req.max_new_tokens + self.spec_k
        if total > self.max_seq_len:
            with self._lock:  # submit() is the documented any-thread path
                self._counters["rejected"] += 1
            spec = (f" (incl. {self.spec_k} positions of speculative "
                    f"verify scratch)" if self.spec_k else "")
            raise ValueError(
                f"request needs {total} KV positions{spec} but the "
                f"engine's static layout holds max_seq_len="
                f"{self.max_seq_len} — shorten the prompt/max_new_tokens "
                f"or size the engine up")
        # bounded admission AFTER the permanent sizing/sampling rejections
        # (those are bugs, not load) and BEFORE the prefix walk, so a shed
        # request never takes refs on shared pages it must then give back
        self._admit(req)
        if self.prefix_cache is not None and not req.is_sampling \
                and not self._prefix_paused:
            # walk the radix tree and take refs on the committed chain NOW
            # (the refs ride the request's lifetime; the scheduler reserves
            # only the pages it must own beyond the shared prefix). Sampled
            # requests take the classic logits-returning prefill and skip
            # sharing — the window step returns argmaxes, not logits rows.
            req.shared_pages, req.shared_kv, req.shared_len = \
                self.prefix_cache.share(req.prompt)
        self._scheduler.submit(req)
        trace.event("engine.submit", rid=req.rid,
                    prompt_len=int(req.prompt.size),
                    max_new=req.max_new_tokens)
        return req

    # ------------------------------------------------------------------
    # overload control: bounded admission + the degradation ladder
    # ------------------------------------------------------------------
    def _admit(self, req: Request) -> None:
        """The overload front door, called from submit() for every request
        that passed the permanent (sizing/sampling) checks. Sheds with the
        typed EngineOverloaded when (a) the queue is at max_queue — the
        hard cap that bounds both memory and worst-case queue wait — or
        (b) the projected queue wait at the measured token rate already
        exceeds the request's TTL (or PT_SERVE_SHED_TTL for TTL-less
        requests): queueing it would only burn its whole deadline before
        a RequestTimeout, so rejecting NOW costs the client nothing and
        the engine a queue slot."""
        depth = self._scheduler.queue_depth
        if depth >= self.max_queue:
            self._shed(req, depth,
                       f"queue at max_queue={self.max_queue}")
        if self.shed_ttl <= 0:
            return  # deadline-aware shedding is opt-in (knob off)
        budget = req.deadline.timeout
        if budget is None:
            budget = self.shed_ttl
        if budget is not None and budget > 0:
            wait = self._projected_wait(req.max_new_tokens)
            if wait is not None and wait > budget:
                self._shed(req, depth,
                           f"projected queue wait {wait:.3g}s exceeds the "
                           f"{budget:.3g}s deadline budget")

    def _measured_rate(self) -> Optional[float]:
        """Tokens/sec actually measured over this engine's lifetime (all
        prefill + decode time), or None on a cold engine — a cold engine
        never deadline-sheds, because an estimate from nothing would shed
        the very first burst for no reason."""
        gen_time = self._decode_time + self._prefill_time
        toks = self._counters["tokens_generated"]
        if gen_time <= 0 or toks <= 0:
            return None
        return toks / gen_time

    def _projected_wait(self, new_tokens: int) -> Optional[float]:
        """Seconds until a request submitted NOW would finish: the whole
        outstanding backlog plus its own tokens, over the measured rate.
        Deliberately conservative (FIFO drain, no occupancy modeling) —
        the shed must be cheap, not clairvoyant."""
        rate = self._measured_rate()
        if rate is None:
            return None
        return (self._scheduler.backlog_tokens() + new_tokens) / rate

    def _retry_after_ms(self) -> int:
        """Advice for the 429: the time one queue slot should take to
        drain at the measured rate — backlog over (queue depth + active),
        clamped to [1ms, 60s]. Cold engines advise a flat 100ms."""
        rate = self._measured_rate()
        if rate is None:
            return 100
        inflight = self._scheduler.queue_depth + self._scheduler.active
        per_slot = self._scheduler.backlog_tokens() / max(1, inflight)
        return max(1, min(60_000, int(1000.0 * per_slot / rate)))

    def _shed(self, req: Request, depth: int, why: str) -> None:
        with self._lock:
            self._counters["rejected"] += 1
            self._counters["shed"] += 1
        retry_ms = self._retry_after_ms()
        # stamp the ring BEFORE constructing the error: EngineOverloaded's
        # construction fires the flight-recorder incident hook, and the
        # snapshot it takes must already contain this shed event
        trace.event("engine.shed", rid=req.rid, level=self._pressure,
                    queued=depth, retry_after_ms=retry_ms, reason=why)
        raise EngineOverloaded(
            f"serving request {req.rid}", req.deadline.timeout,
            detail=f"{why}; retry after {retry_ms}ms",
            retry_after_ms=retry_ms)

    def _update_pressure(self) -> None:
        """Walk the degradation ladder (called under self._lock at the top
        of every step). Pressure = queue depth over max_queue; levels are
        entered at _LADDER_ENTER and left below _LADDER_EXIT (hysteresis),
        each transition stamped on the trace ring."""
        faultpoint(FP_PRESSURE)
        ratio = self._scheduler.queue_depth / float(self.max_queue)
        level = self._pressure
        new = level
        while new < 3 and ratio >= _LADDER_ENTER[new + 1]:
            new += 1
        while new > 0 and ratio < _LADDER_EXIT[new]:
            new -= 1
        if new != level:
            trace.event("engine.pressure", level=new, prev=level,
                        queued=self._scheduler.queue_depth,
                        ratio=round(ratio, 4))
            if new > level:
                self._enter_pressure(level, new)
            else:
                self._exit_pressure(level, new)
            self._pressure = new
        self._level_steps[self._pressure] += 1

    def _enter_pressure(self, old: int, new: int) -> None:
        if new >= 1 and not self._prefix_paused:
            # level 1: trim the prefix-sharing radix tree — cached-prefix
            # pages are a latency optimization, and under pressure their
            # capacity serves admission instead
            self._prefix_paused = True
            if self.prefix_cache is not None:
                self._counters["pressure_trims"] += 1
                self.prefix_cache.evict(self.pool.total_pages)
        if new >= 2 and not self._spec_paused and self.spec_k:
            # level 2: disable speculative decoding and hand back every
            # reservation's verify-scratch pages — spec is a throughput
            # optimization whose scratch capacity now admits real requests
            self._spec_paused = True
            self._counters["spec_pauses"] += 1
            freed = self._scheduler.shed_reserve_extra()
            self._counters["scratch_pages_returned"] += freed
        # level 3 sheds nothing more: the prefill budget already runs one
        # prefill call a step while slots decode

    def _exit_pressure(self, old: int, new: int) -> None:
        if new < 2 and self._spec_paused:
            self._spec_paused = False
            self._scheduler.restore_reserve_extra(self.spec_k)
        if new < 1 and self._prefix_paused:
            self._prefix_paused = False

    def _spec_ok(self) -> bool:
        """Speculative decode runs only when every DECODING slot still
        owns its verify scratch: a request admitted while level 2 shed
        the reserve has no capacity for the k-token verify window, so the
        whole batch decodes classically until those requests drain."""
        if not self.spec_k or self._spec_paused:
            return False
        return all(r.scratch_reserved
                   for r in self._scheduler.running().values()
                   if r.state is RequestState.DECODING)

    @property
    def kv_bytes_per_position(self) -> int:
        """Bytes one attention position of one slot holds, all layers."""
        return self._cache_bytes["kv"] // (self.max_batch * self.max_seq_len)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one slot holds whatever its length."""
        return self._cache_bytes["state"] // self.max_batch

    @property
    def window_bytes_per_slot(self) -> int:
        """Bytes of sliding-window rings one slot holds whatever its
        length."""
        return self._cache_bytes["window"] // self.max_batch

    @property
    def pressure_level(self) -> int:
        """Current degradation-ladder level, 0 (healthy) .. 3 (shedding
        everything optional). Read by the gateway's HEALTH verb."""
        return self._pressure

    # ---- what a driver asks between two steps: bookkeeping a step in
    # flight cannot change (only the scheduler pass moves it), so these do
    # not settle and take no lock of the engine's
    @property
    def idle(self) -> bool:
        """No request queued or running."""
        return self._scheduler.idle

    @property
    def queue_depth(self) -> int:
        return self._scheduler.queue_depth

    @property
    def active(self) -> int:
        return self._scheduler.active

    # ---- the handles a reader from outside takes: each settles first
    @property
    def scheduler(self) -> ContinuousBatchingScheduler:
        self.settle()
        return self._scheduler

    @property
    def _caches(self):
        self.settle()
        return self._slot_caches

    def settle(self) -> int:
        """Read the decode step in flight, if any, emit its tokens and
        launch nothing: afterwards the caches have consumed all but each
        request's newest token. Returns the tokens emitted."""
        with self._lock:
            return self._settle("outside_read")

    def _settle(self, cause: str) -> int:
        """`settle()` under the lock, for `cause` (SETTLE_CAUSES)."""
        flight = self._flight
        if flight is None:
            return 0
        t0 = time.perf_counter()
        with _span("engine.settle", lambda: dict(
                cause=cause, step=flight.index,
                rids=[r.rid for _, r in flight.rows])):
            made = self._collect(flight, cause)
        self._decode_time += time.perf_counter() - t0
        return made

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: scheduler pass (evict/expire/join) ->
        prefill calls within the budget (the module docstring) -> launch
        ONE batched decode step for every active slot -> read the step
        launched before it (see the class docstring: a step may be in
        flight when this returns). Returns the number of tokens emitted."""
        with self._lock, self._step_span():
            self._update_pressure()
            joined, evicted = self._scheduler.schedule()
            for req in evicted:
                # a TTL eviction before its prefill finished drops its
                # caches here, strictly between steps (pages went back via
                # the scheduler; uncommitted ones never entered the tree)
                if req in self._prefilling:
                    self._prefilling.remove(req)
                req.scratch = None
                req.shared_kv = []
                if self.drafter is not None:
                    # a slot holding in-flight draft state gives it back
                    # here, strictly between steps — the verify signature
                    # and everyone else's tokens never notice
                    self.drafter.on_evict(req)
            for req in joined:
                self._join(req)
            produced = self._prefills()
            # listed here, in engine.step's own time: in a profiler's trace
            # nothing of a decode then lies outside a span of the program
            active = self._active_slots()
            if active and self._spec_ok():
                produced += self._decode_speculative(active)
            elif active or self._flight is not None:
                produced += self._decode(active)
            return produced

    def _step_span(self):
        """`engine.step`, the parent of everything one iteration records;
        its self time is the scheduler pass, the pressure ladder and the
        joins. An idle engine polled by its driver records nothing: a span
        a poll would churn the ring out of the records a postmortem needs."""
        if trace.enabled() and not self._scheduler.idle:
            return trace.span("engine.step")
        return _NO_SPAN

    def run(self, poll: float = 0.0) -> None:
        """Drive step() until no request is queued or running. `poll`
        sleeps between empty iterations (submissions from other threads)."""
        while not self._scheduler.idle:
            made = self.step()
            if made == 0 and poll:
                time.sleep(poll)
        self.settle()   # rows computed for requests that ended meanwhile

    def generate(self, prompts: Sequence, max_new_tokens: int = 16,
                 ttl: Optional[float] = None) -> List[np.ndarray]:
        """Batch convenience: submit every prompt, drain, return
        prompt+generated arrays in submission order (typed errors
        propagate from the failing request)."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens, ttl=ttl)
                for p in prompts]
        self.run()
        return [r.result() for r in reqs]

    # ------------------------------------------------------------------
    def _run_step(self, step_fn, args, logits: bool):
        """One call of a slot step: (next tokens, the logits rows or None,
        the model's counters or None, the caches)."""
        outs = step_fn(*args)
        counted = outs[-2] if self._step_counters else None
        return outs[0], (outs[1] if logits else None), counted, outs[-1]

    def _bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if b >= plen:
                return b
        return self.max_seq_len

    def _ensure_logits_step(self):
        """The sampling slot-step variant (argmax AND last-token logits
        row), asked for on first need: greedy-only traffic never lowers it,
        so the frozen-lowering join contract for greedy engines is
        untouched."""
        return compiled_step(self.model, "slot_logits")

    def _ensure_window_fn(self):
        """The [B, W] window step (the model's verify step): scores every
        window position at a per-row offset with exact causal masking, which
        is precisely a chunk of prefill. Asked for on first need, so engines
        that never share a prefix never add its lowering."""
        if self._window_fn is None:
            self._window_fn = compiled_step(self.model, "verify")
        return self._window_fn

    def _join(self, req: Request) -> None:
        """A joiner enters the prefill queue; with prefix sharing on it
        walks the tree a second time first."""
        if self.prefix_cache is not None and not req.is_sampling \
                and req.shared_len == 0 and not self._prefix_paused:
            # second walk at JOIN time: a request submitted alongside its
            # donor missed the tree at submit (the donor had not committed
            # yet) — by the join pass it has. The refs replace an equal
            # count of already-reserved own pages, which go back to the
            # pool, so the accounting saving is as real as the compute one.
            pages, kvs, slen = self.prefix_cache.share(req.prompt)
            if slen:
                req.shared_pages, req.shared_kv, req.shared_len = \
                    pages, kvs, slen
                surplus = req.pages[:len(pages)]
                req.pages = req.pages[len(pages):]
                self.pool.release(surplus)
        self._prefilling.append(req)

    def _prefills(self) -> int:
        """The step's prefill calls: one for every queued joiner when no
        slot decodes, else one for the first in join order and none for
        the others (each counted in `prefill_deferred`)."""
        decoding = bool(self._active_slots())
        produced = calls = 0
        for req in list(self._prefilling):
            if decoding and calls:
                self._counters["prefill_deferred"] += 1
                continue
            produced += self._prefill_call(req, decoding)
            calls += 1
            if req.state is not RequestState.PREFILL:
                self._prefilling.remove(req)
        return produced

    def _prefill_call(self, req: Request, decoding: bool) -> int:
        """One prefill call for `req`: a shared-prefix tail's next scratch
        window, a cut prompt's next piece, or the whole prompt."""
        if req.shared_len:
            if req.scratch is None:
                self._begin_scratch(req)
            # a scratch window keeps the order it had: the step in flight
            # is read before it runs
            return self._settle("prefill") + self._advance_one(req)
        if req.scratch is None:
            if not self._cuts(int(req.prompt.size), decoding):
                return self._prefill(req)
            req.scratch = _zero_caches(*self._zero_args)
            self._counters["chunked_prefills"] += 1
        if int(req.prompt.size) - req.prefill_pos > self._cut:
            self._prefill_piece(req)
            return 0
        return self._prefill(req, req.prefill_pos, req.scratch)

    def _cuts(self, plen: int, decoding: bool) -> bool:
        """Whether a prompt of `plen` is cut: longer than C, a slot decoding
        (or C given), and its last piece's window inside the cache (a
        window past S_max would be clamped onto real rows)."""
        c = self._cut
        if not c or plen <= c or not (decoding or self.prefill_chunk):
            return False
        last = (plen - 1) // c * c
        return last + self._piece_width(plen - last) <= self.max_seq_len

    def _piece_width(self, rest: int) -> int:
        """A last piece's window: the smallest bucket that holds the rest
        of the prompt, at most C (which a given chunk need not be)."""
        return min(self._bucket_for(rest), self._cut)

    def _prefill_piece(self, req: Request) -> None:
        """A piece of a cut prompt that is not its last: C positions at
        `prefill_pos` through the slot step over the request's own caches,
        launched and not waited on (its token is not read)."""
        t0 = time.perf_counter()
        pos, c = req.prefill_pos, self._cut
        with trace.span("engine.prefill_chunk", rid=req.rid, pos=pos,
                        tokens=c):
            tok = np.asarray(req.prompt[pos:pos + c], np.int64)[None]
            nxt, _, counted, req.scratch = self._run_step(
                self._step_fn, (self._params, jnp.asarray(tok), req.scratch,
                                jnp.asarray([pos], jnp.int32),
                                self._piece_last), False)
            nxt = _array(nxt)
            _launched("prefill_chunk", nxt,
                      lambda: dict(rid=req.rid, pos=pos, tokens=c))
            if counted is not None:
                trace.done(nxt)
                self._counted += np.asarray(counted)
        req.prefill_pos = pos + c
        self._counters["prefill_chunks"] += 1
        self._counters["prefill_positions"] += c
        self._counters["prefill_positions_padded"] += c
        self._prefill_time += time.perf_counter() - t0

    def _begin_scratch(self, req: Request) -> None:
        """A shared-prefix tail's per-request [1, S_max + W] caches,
        assembled on the host: zeros, with the shared chain's committed
        page rows in place — the windows then compute only the O(suffix)
        tail (positions shared_len..plen)."""
        ps = self.pool.page_size
        shape = (1, self._scratch_len) + self._cache_shape[1:]
        scratch = []
        for li in range(len(self._slot_caches)):
            k = np.zeros(shape, self._cache_dtype)
            v = np.zeros(shape, self._cache_dtype)
            for pi, page_kv in enumerate(req.shared_kv):
                k[0, pi * ps:(pi + 1) * ps] = page_kv[li][0]
                v[0, pi * ps:(pi + 1) * ps] = page_kv[li][1]
            scratch.append((jnp.asarray(k), jnp.asarray(v)))
        req.scratch = scratch
        req.prefill_pos = req.shared_len
        self._counters["shared_prefix_joins"] += 1
        self._counters["prefill_pages_saved"] += len(req.shared_pages)

    def _advance_one(self, req: Request) -> int:
        """One [1, W] window of prefill for one scratch request: positions
        prefill_pos..prefill_pos+n land in its scratch caches (the window
        may spill into the S_pad tail — sliced off at the slot write). The
        final window's argmax at the last REAL token is the request's
        first generated token, bitwise the bucketed path's (the verify
        step's sequential-equivalence contract)."""
        t0 = time.perf_counter()
        w = self._window
        plen = int(req.prompt.size)
        pos = req.prefill_pos
        n = min(w, plen - pos)
        with trace.span("engine.prefill_chunk", rid=req.rid, pos=pos,
                        tokens=n):
            tok = np.zeros((1, w), np.int64)
            tok[0, :n] = req.prompt[pos:pos + n]
            nxt, req.scratch = self._ensure_window_fn()(
                self._params, jnp.asarray(tok), req.scratch,
                jnp.asarray([pos], jnp.int32))
            nxt = _array(nxt)
            _launched("window", nxt,
                      lambda: dict(rid=req.rid, pos=pos, tokens=n))
            self._counters["prefill_positions"] += n
            self._counters["prefill_positions_padded"] += w
            req.prefill_pos = pos + n
            made = 0
            if req.prefill_pos >= plen:
                trace.done(nxt)
                made = self._finish_scratch_prefill(
                    req, int(np.asarray(nxt)[0, n - 1]))
        self._prefill_time += time.perf_counter() - t0
        return made

    def _finish_scratch_prefill(self, req: Request, first: int) -> int:
        """Scratch prefill complete: commit the prompt's full pages into
        the prefix tree (host copies from scratch, which the slot write
        below does not donate), write the slot row, start decoding."""
        plen = int(req.prompt.size)
        if self.prefix_cache is not None:
            scratch = req.scratch
            ps = self.pool.page_size

            def kv_of_page(i):
                return [(np.asarray(sk[0, i * ps:(i + 1) * ps]),
                         np.asarray(sv[0, i * ps:(i + 1) * ps]))
                        for sk, sv in scratch]

            self._commit_prefix(req, kv_of_page)
        self._slot_caches = _write_scratch(self._slot_caches, req.scratch,
                                      jnp.asarray(req.slot, jnp.int32))
        req.scratch = None
        req.shared_kv = []
        req.cache_len = plen
        req.state = RequestState.DECODING
        if not req.append_token(first):
            req.next_token = first
        if self.drafter is not None:
            self.drafter.on_join(req)
        self._counters["prefills"] += 1
        self._counters["tokens_generated"] += 1
        return 1

    def _commit_prefix(self, req: Request, kv_of_page) -> None:
        """Mark the request's own pages covering full-prompt chunks as
        committed (share()-able from here on — the pool-level guard that
        an in-flight prefill's pages never enter the tree) and insert the
        chunks into the radix tree, which takes its own refs."""
        if self._prefix_paused:
            return  # ladder level 1+: don't regrow the tree we just shed
        ps = self.pool.page_size
        n_full = int(req.prompt.size) // ps
        base = req.shared_len // ps
        own = req.pages[:max(0, n_full - base)]
        if own:
            self.pool.commit(own)
        self.prefix_cache.insert(req.prompt, req.shared_len, own, kv_of_page)

    def _prefill(self, req: Request, pos: int = 0, caches=None) -> int:
        """Run the joiner's prompt through the captured step at its bucket
        length (batch 1, fresh zero caches), write the KV rows into its
        slot, and sample its first token (argmax on device for greedy
        requests; host-side off the logits row for sampled ones). From
        `pos` on, over `caches`, it is a cut prompt's last piece."""
        t0 = time.perf_counter()
        plen = req.prompt.size
        rest = plen - pos
        bucket = self._piece_width(rest) if pos else self._bucket_for(plen)
        with _span("engine.prefill", lambda: dict(
                rid=req.rid, bucket=bucket, prompt_len=int(plen),
                pad=int(bucket - rest), pos=pos)):
            with trace.span("engine.prefill.prep", rid=req.rid):
                tok = np.zeros((1, bucket), np.int64)
                tok[0, :rest] = req.prompt[pos:]
                pref_caches = _zero_caches(*self._zero_args) \
                    if caches is None else caches
                args = (self._params, jnp.asarray(tok), pref_caches,
                        jnp.asarray([pos], jnp.int32) if pos
                        else self._prefill_off,
                        jnp.asarray([rest - 1], jnp.int32))
            with trace.span("engine.prefill.launch", rid=req.rid):
                nxt, logits, counted, pref_out = self._run_step(
                    self._ensure_logits_step() if req.is_sampling
                    else self._step_fn, args, req.is_sampling)
                nxt = _array(nxt)
                _launched("prefill", nxt, lambda: dict(
                    rid=req.rid, bucket=bucket, pos=pos))
            with trace.span("engine.prefill.wait", rid=req.rid):
                # the host blocked on the device: first the decode step in
                # flight, which runs before this call, is read and emitted
                # (its tokens do not wait out this call, so no gap between
                # two tokens holds two prefill calls: a piece launched the
                # step before lies under that step already); its seconds
                # are the decode's
                t1 = time.perf_counter()
                made = self._settle("prefill")
                t0 += time.perf_counter() - t1
                # then the first token's download (a sampled request's
                # logits row, drawn from in the commit)
                trace.done(nxt)
                got = np.asarray(logits)[0] if req.is_sampling \
                    else int(np.asarray(nxt)[0])
                if counted is not None:
                    self._counted += np.asarray(counted)
            with trace.span("engine.prefill.commit", rid=req.rid):
                if req.is_sampling:
                    first = self._sample_row(req, got)
                    self._counters["sampled_tokens"] += 1
                else:
                    first = got
                self._slot_caches = _write_slot(self._slot_caches, pref_out,
                                           jnp.asarray(req.slot, jnp.int32))
                if self.prefix_cache is not None:
                    # donor commit: the prompt's full pages enter the radix
                    # tree (host copies from pref_out, which the slot write
                    # above did not donate) so the NEXT request over this
                    # prefix prefills only its tail. KV rows are sampling-
                    # independent, so sampled requests donate too.
                    ps = self.pool.page_size

                    def kv_of_page(i):
                        return [(np.asarray(pk[0, i * ps:(i + 1) * ps]),
                                 np.asarray(pv[0, i * ps:(i + 1) * ps]))
                                for pk, pv in pref_out]

                    self._commit_prefix(req, kv_of_page)
                req.cache_len = plen
                req.state = RequestState.DECODING
                if not req.append_token(first):
                    req.next_token = first
                if self.drafter is not None:
                    self.drafter.on_join(req)
                req.scratch = None
                self._counters["prefills"] += 1
                self._counters["prefill_chunks"] += bool(pos)
                self._counters["tokens_generated"] += 1
                self._counters["prefill_positions"] += int(rest)
                self._counters["prefill_positions_padded"] += bucket
        self._prefill_time += time.perf_counter() - t0
        return 1 + made

    def _active_slots(self):
        return [(s, r) for s, r in sorted(self._scheduler.running().items())
                if r.state is RequestState.DECODING
                and r.finish_reason is None]

    def _decode(self, active) -> int:
        """Launch one [max_batch, 1] decode step over every active slot that
        goes on, and read the step launched before it: step i+1's call
        (`engine.decode.launch`) comes BEFORE the block on step i's tokens
        (`engine.decode.wait`), so the host's work lies under the device's
        step. Inactive slots feed token 0 at offset 0 — their rows are
        garbage the ragged length vector keeps out of everyone else's
        attention, and the next prefill overwrites them wholesale; so is the
        row of a request that ended while its step was in flight.

        Greedy slots take the on-device argmax ([B] i32 to host); sampled
        slots re-draw host-side from their logits row — the logits-
        returning step variant only runs on steps where a sampled slot is
        active, and its greedy rows ride the SAME on-device argmax, so
        greedy streams are bitwise identical either way. Such a step, and
        every step of an engine with a drafter, is read before this returns
        and the step in flight before it is launched: the host needs its
        tokens to make the next input."""
        t0 = time.perf_counter()
        flight = self._flight
        # who goes on is known without the tokens in flight: a request whose
        # token in flight is its last by max_new_tokens is left out
        rows = [(s, r) for s, r in active
                if len(r.output_tokens) + (flight is not None and
                                           flight.holds(s, r))
                < r.max_new_tokens]
        sampling = any(r.is_sampling for _, r in rows)
        sync = sampling or self.drafter is not None
        ahead = bool(rows) and flight is not None and not sync
        # why nothing is in flight once a step is read without one behind it
        why = ("sampling" if sampling else "speculative") if sync else "empty"
        produced = 0
        # the decode hot path: the per-step rid list exists only when
        # tracing is on — off, every span is the shared no-op singleton
        with _span("engine.decode_step", lambda: dict(
                step=self._launched if rows else flight.index,
                rids=[r.rid for _, r in rows or flight.rows], ahead=ahead)):
            if flight is not None and not ahead:
                produced += self._collect(flight, why)
                flight = None
            if rows:
                self._launch(rows, flight, sampling)
            if flight is not None:
                produced += self._collect(flight, None)
            if rows and sync:
                produced += self._collect(self._flight, why)
        self._decode_time += time.perf_counter() - t0
        return produced

    def _launch(self, rows, flight: Optional[_Flight], sampling: bool):
        """Prep and call the slot step for `rows`, leaving it in flight. A
        row that was in `flight` too takes its token from that step's output
        on the device, every other its request's `next_token`; offsets are
        host arithmetic, advanced here and not when the tokens are read."""
        b = self.max_batch
        with trace.span("engine.decode.prep"):
            tok = np.zeros((b,), self._tok_dtype)
            from_host = np.ones((b,), np.bool_)
            off = np.zeros((b,), np.int32)
            for s, r in rows:
                off[s] = r.cache_len
                if flight is not None and flight.holds(s, r):
                    from_host[s] = False
                else:
                    tok[s] = r.next_token
            prev = self._no_tokens if flight is None else flight.nxt
            args = (self._params, self._merge(from_host, tok, prev),
                    self._slot_caches, jnp.asarray(off),
                    self._decode_last_pos)
        with trace.span("engine.decode.launch"):
            nxt, logits, counted, self._slot_caches = self._run_step(
                self._ensure_logits_step() if sampling
                else self._step_fn, args, sampling)
            self._flight = _Flight(self._launched, rows, nxt, logits, counted)
            _launched("decode_step", self._flight.nxt, lambda: dict(
                step=self._launched, rids=[r.rid for _, r in rows]))
            for _, r in rows:
                r.cache_len += 1
            self._launched += 1
            if flight is not None:
                self._ahead["launched_ahead"] += 1
            else:
                self._ahead["settled"][self._line] += 1

    def _collect(self, flight: _Flight, cause: Optional[str]) -> int:
        """Block on a launched step's tokens and emit them; `cause` says
        why nothing is in flight afterwards (None: a step is)."""
        if flight is self._flight:
            self._flight = None
        if cause is not None:
            self._line = cause
        with trace.span("engine.decode.wait"):
            # the host blocked on the device: the download began at launch
            trace.done(flight.nxt)
            logit_rows = None if flight.logits is None \
                else np.asarray(flight.logits)
            sampled = np.asarray(flight.nxt)  # [B] i32, not [B, vocab] logits
            if flight.counted is not None:
                # the same program's output, ready with the tokens
                self._counted += np.asarray(flight.counted)
        with trace.span("engine.decode.emit"):
            made = 0
            for s, r in flight.rows:
                if r.finish_reason is not None:
                    # ended while the step was in flight (EOS, TTL): the row
                    # was computed for nothing
                    self._ahead["rows_dropped"] += 1
                    continue
                if r.is_sampling:
                    t = self._sample_row(r, logit_rows[s])
                    self._counters["sampled_tokens"] += 1
                else:
                    t = int(sampled[s])
                if not r.append_token(t):
                    r.next_token = t
                made += 1
            self._counters["decode_steps"] += 1
            self._counters["tokens_generated"] += made
            self._occupancy_sum += len(flight.rows) / float(self.max_batch)
        return made

    def _decode_speculative(self, active) -> int:
        """One drafter pass + ONE [max_batch, k+1] verify call serving
        every active slot: row b carries the slot's pending token followed
        by its k draft proposals at offsets cache_len..cache_len+k. The
        verify returns the greedy argmax at every window position; each
        slot emits the longest draft prefix matching those targets plus
        the bonus target token — 1..k+1 tokens per step, bitwise the
        non-speculative stream. Rejected positions cost nothing to undo:
        the cursor (cache_len) simply doesn't advance past them, their
        cache rows sit beyond every ragged length until overwritten, and
        the pages were reserved for the whole lifetime up front."""
        t0 = time.perf_counter()
        b, k = self.max_batch, self.spec_k
        rids = [r.rid for _, r in active] if trace.enabled() else ()
        produced = 0
        self._line = "speculative"
        self._ahead["settled"]["speculative"] += 1
        self._launched += 1
        with _span("engine.decode_step", lambda: dict(
                step=self._launched - 1, rids=rids, spec=True, ahead=False)):
            with trace.span("engine.decode.prep"):
                drafts = self.drafter.propose(dict(active), k)
                tok = np.zeros((b, k + 1), np.int64)
                off = np.zeros((b,), np.int32)
                for s, r in active:
                    tok[s, 0] = r.next_token
                    tok[s, 1:] = drafts[s]
                    off[s] = r.cache_len
                args = (self._params, jnp.asarray(tok), self._slot_caches,
                        jnp.asarray(off))
            with _span("engine.verify_step", lambda: dict(k=k, rids=rids)):
                with trace.span("engine.decode.launch"):
                    nxt, self._slot_caches = self._verify_fn(*args)
                    nxt = _array(nxt)
                    _launched("verify_step", nxt, lambda: dict(
                        step=self._launched - 1, rids=rids))
                with trace.span("engine.decode.wait"):
                    trace.done(nxt)
                    targets = np.asarray(nxt)   # [B, k+1] i32, one sync
            with trace.span("engine.decode.emit"):
                for s, r in active:
                    d = drafts[s]
                    m = 0
                    while m < k and int(d[m]) == int(targets[s, m]):
                        m += 1
                    emitted = 0
                    for i in range(m + 1):
                        t = int(targets[s, i])
                        emitted += 1
                        if r.append_token(t):
                            break
                        r.next_token = t
                    r.cache_len += emitted
                    self.drafter.observe(r, emitted)
                    self._accept_hist[emitted] += 1
                    self._counters["draft_tokens_proposed"] += k
                    self._counters["draft_tokens_accepted"] += m
                    produced += emitted
                self._counters["decode_steps"] += 1
                self._counters["verify_steps"] += 1
                self._counters["tokens_generated"] += produced
                self._occupancy_sum += len(active) / float(b)
        self._decode_time += time.perf_counter() - t0
        return produced

    def _sample_row(self, req: Request, row) -> int:
        """Host-side per-slot sampling from one logits row: temperature
        scaling, optional top_p nucleus truncation (smallest prefix of the
        sorted distribution reaching top_p), then one draw from the
        request's own deterministic Generator — rows are independent, so a
        sampled slot never perturbs its greedy neighbors."""
        logits = np.asarray(row, np.float64) / float(req.temperature)
        logits -= logits.max()
        probs = np.exp(logits)
        probs /= probs.sum()
        if req.top_p is not None:
            order = np.argsort(-probs, kind="stable")
            cum = np.cumsum(probs[order])
            cut = int(np.searchsorted(cum, float(req.top_p))) + 1
            keep = order[:cut]
            p = probs[keep] / probs[keep].sum()
            return int(keep[req.rng.choice(len(keep), p=p)])
        return int(req.rng.choice(len(probs), p=probs))

    # ------------------------------------------------------------------
    # introspection (profiler.serving_summary reads this)
    # ------------------------------------------------------------------
    def info(self) -> dict:
        self.settle()
        c = dict(self._counters)
        steps = c["decode_steps"]
        gen_time = self._decode_time + self._prefill_time
        sched = self._scheduler.info()
        step_info = getattr(self._step_fn, "cache_info", dict)()
        decode = (self.max_batch, 1)
        out = {
            "max_batch": self.max_batch,
            "max_seq_len": self.max_seq_len,
            "prefill_buckets": list(self.buckets),
            **{k: sched[k] for k in ("submitted", "admitted", "finished",
                                     "timed_out", "evicted", "active",
                                     "queued")},
            "rejected": c["rejected"] + sched["rejected"],
            "prefills": c["prefills"],
            "decode_steps": steps,
            "tokens_generated": c["tokens_generated"],
            "sampled_tokens": c["sampled_tokens"],
            "avg_occupancy": self._occupancy_sum / steps if steps else 0.0,
            "tokens_per_sec": c["tokens_generated"] / gen_time
            if gen_time else 0.0,
            "prefill_chunk": self.prefill_chunk,
            # the prefill budget: C (0: nothing is cut), the prompts cut and
            # their calls, and the steps a joiner waited behind another call
            "prefill_cut": self._cut,
            "prefill_chunks": c["prefill_chunks"],
            "chunked_prefills": c["chunked_prefills"],
            "prefill_deferred": c["prefill_deferred"],
            "shared_prefix_joins": c["shared_prefix_joins"],
            "prefill_pages_saved": c["prefill_pages_saved"],
            # positions prefills computed, real and with their padding (a
            # bucket's right pad, a window's tail)
            "prefill_positions": c["prefill_positions"],
            "prefill_positions_padded": c["prefill_positions_padded"],
            "cache_bytes": dict(self._cache_bytes),
            "kv_bytes_per_position": self.kv_bytes_per_position,
            "state_bytes_per_slot": self.state_bytes_per_slot,
            "window_bytes_per_slot": self.window_bytes_per_slot,
            **_split_counters(self._step_counters, self._counted),
            "pool": self.pool.info(),
            # steps launched before the one before was read, of all decode
            # steps; the others by the cause that left nothing in flight
            "decode_ahead": {
                "launched_ahead": self._ahead["launched_ahead"],
                "decode_steps": steps,
                "settled": dict(self._ahead["settled"]),
                "rows_dropped": self._ahead["rows_dropped"]},
            "step": {**step_info,
                     "kv_write": _ops_taken(self._step_fn, decode,
                                            **_KV_WRITE),
                     "ssm_update": _ops_taken(self._step_fn, decode,
                                              **_SSM_UPDATE)},
            "pressure": {
                "level": self._pressure,
                "max_queue": self.max_queue,
                "shed": c["shed"],
                "pressure_trims": c["pressure_trims"],
                "spec_pauses": c["spec_pauses"],
                "scratch_pages_returned": c["scratch_pages_returned"],
                "spec_paused": int(self._spec_paused),
                "prefix_paused": int(self._prefix_paused),
                **{f"level{i}_steps": n
                   for i, n in enumerate(self._level_steps)},
            },
        }
        if self.prefix_cache is not None:
            out["prefix"] = self.prefix_cache.info()
        if self._window_fn is not None:
            out["window"] = {
                "size": self._window,
                **getattr(self._window_fn, "cache_info", dict)()}
        if self.spec_k:
            proposed = c["draft_tokens_proposed"]
            verifies = c["verify_steps"]
            emitted = sum(i * n for i, n in enumerate(self._accept_hist))
            slots_verified = sum(self._accept_hist)
            out["spec"] = {
                "k": self.spec_k,
                "drafter": self.drafter.info(),
                "verify_steps": verifies,
                "draft_steps": getattr(self.drafter, "draft_calls", 0),
                "draft_tokens_proposed": proposed,
                "draft_tokens_accepted": c["draft_tokens_accepted"],
                "acceptance_rate": c["draft_tokens_accepted"] / proposed
                if proposed else 0.0,
                "tokens_per_verify": emitted / slots_verified
                if slots_verified else 0.0,
                "tokens_per_verify_hist": list(self._accept_hist),
                "verify": {
                    **getattr(self._verify_fn, "cache_info", dict)(),
                    "kv_write": _ops_taken(
                        self._verify_fn, (self.max_batch, self.spec_k + 1),
                        **_KV_WRITE)},
            }
        return out


def _split_counters(names, vector) -> dict:
    """{name: int, or a list where the name has several entries}."""
    out, at = {}, 0
    for name, n in names:
        part = [int(v) for v in vector[at:at + n]]
        out[name] = part[0] if n == 1 else part
        at += n
    return out


# which K/V write the attention layers took: the in-place row write
# (`ops/pallas/kv_cache_append.py`) or the vmapped `dynamic_update_slice`,
# as `models/llama.py` names the op
_KV_WRITE = {"kernel": "kv_cache_append", "scatter": "kv_cache_upd"}
# which one-position update the Mamba layers took: the in-place kernel
# (`ops/pallas/selective_scan.py ssm_decode_update`) or the jnp fusion, as
# `models/jamba.py` names the op
_SSM_UPDATE = {"kernel": "ssm_decode_update", "fusion": "ssm_decode_fusion"}


def _ops_taken(step_fn, tok_shape, **ops) -> dict:
    """{key: how many times `step_fn`'s captured program at the token window
    `tok_shape` dispatched the op named `ops[key]`}, counted as it was
    traced (a model that names none reads 0 each).  Empty until that
    program is captured, and under plain jit."""
    for prog in getattr(step_fn, "programs", list)():
        if tok_shape in [a.shape for a in prog.in_avals]:
            counts = prog.op_counts()
            return {key: counts.get(op, 0) for key, op in ops.items()}
    return {}


def serving_info() -> List[dict]:
    """info() of every live engine (profiler.serving_summary's source)."""
    return [e.info() for e in list(_ENGINES)]
