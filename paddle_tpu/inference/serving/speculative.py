"""Speculative decoding drafters for the serving engine.

Leviathan et al.'s greedy speculative sampling (PAPERS.md): a cheap
drafter proposes ``k`` tokens per active slot, the target model scores
all ``k+1`` window positions in ONE captured verify call (the model's
verify step, `models/steps.py`), and the engine accepts the
longest draft prefix matching the target's argmax plus the one bonus
token the verify already paid for. Greedy verification makes the drafter
pure OPPORTUNITY: a wrong draft costs window slots, never correctness —
the emitted stream is bitwise the non-speculative engine's, whatever the
drafter proposes (tests/test_serving.py asserts it for both backends).

Two backends:

- ``NGramDrafter`` (default, ``PT_SERVE_DRAFTER=ngram``): prompt-lookup /
  n-gram continuation. Zero extra weights, O(1) host work per token: a
  per-request hash index maps every suffix n-gram (n <= max_n) of the
  request's prompt+output stream to its most recent earlier occurrence;
  propose() replays the continuation of the longest suffix match and
  falls back to repeating the last token (exactly right for the run-
  heavy streams greedy decoding produces). This is the zero-cost default
  because its proposals are free relative to one model call.

- ``DraftModelDrafter`` (``PT_SERVE_DRAFTER=model``): a shrunk-config
  target-family model with its own KV caches over the same batch-slot
  layout, driven through the same captured [B, 1] slot step the engine
  uses. Proposing k tokens costs k draft-model calls (batched over every
  active slot), so it pays off when the draft is much smaller than the
  target AND predicts it well (a trained pair); the n-gram backend is
  the right choice for the CPU proxy.

Draft-side cache coherence rides cursor arithmetic like the target's:
``observe()`` advances the draft cursor over positions whose K/V are
known true (catch-up feeds + accepted proposals); rejected positions are
simply re-fed next round. Nothing is ever repaired in place.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

from ...models.steps import compiled_step

__all__ = ["Drafter", "NGramDrafter", "DraftModelDrafter", "build_drafter"]


class Drafter:
    """Drafter contract (all host-side; called between decode steps only).

    The engine guarantees: ``on_join`` after a request's prefill (prompt
    and first output token already in ``req``), ``propose`` once per
    speculative decode step with every DECODING slot, ``observe`` with
    the number of tokens the verify accepted, ``on_evict`` when the slot
    is released. Proposals must be exactly ``k`` tokens per slot (the
    verify signature is fixed at [max_batch, k+1])."""

    kind = "none"

    def on_join(self, req) -> None:
        raise NotImplementedError

    def propose(self, active: Dict[int, object], k: int) -> Dict[int, List[int]]:
        """slot -> exactly-k proposed continuation tokens."""
        raise NotImplementedError

    def observe(self, req, n_accepted: int) -> None:
        """``n_accepted`` tokens were emitted for ``req`` this step (its
        ``output_tokens``/``cache_len`` are already advanced)."""
        raise NotImplementedError

    def on_evict(self, req) -> None:
        raise NotImplementedError

    def info(self) -> dict:
        return {"kind": self.kind}


class _NGramIndex:
    """Suffix n-gram -> most recent EARLIER occurrence, O(1) per token.

    ``maps[n][gram] = position just past the gram``; extending by one
    token updates max_n entries. ``prev`` keeps the previous position for
    the gram that is currently the stream suffix, so a suffix that only
    matches itself still finds its last earlier occurrence."""

    __slots__ = ("toks", "maps", "prev", "max_n")

    def __init__(self, toks, max_n: int):
        self.toks: List[int] = []
        self.maps = [None] + [dict() for _ in range(max_n)]
        self.prev = [None] + [dict() for _ in range(max_n)]
        self.max_n = max_n
        self.extend(toks)

    def extend(self, toks) -> None:
        for t in toks:
            self.toks.append(int(t))
            L = len(self.toks)
            for n in range(1, self.max_n + 1):
                if L < n:
                    break
                gram = tuple(self.toks[L - n:L])
                m = self.maps[n]
                old = m.get(gram)
                if old is not None:
                    self.prev[n][gram] = old
                m[gram] = L

    def propose(self, k: int) -> List[int]:
        toks = self.toks
        L = len(toks)
        for n in range(min(self.max_n, L), 0, -1):
            gram = tuple(toks[L - n:L])
            pos = self.maps[n].get(gram)
            if pos == L:                      # the suffix matched itself
                pos = self.prev[n].get(gram)
            if pos is None:
                continue
            cont = toks[pos:pos + k]
            if cont:
                while len(cont) < k:
                    cont.append(cont[-1])
                return cont
        return [toks[-1] if toks else 0] * k


class NGramDrafter(Drafter):
    """Prompt-lookup drafter: propose the continuation of the longest
    recent n-gram match inside the request's own prompt+output stream."""

    kind = "ngram"

    def __init__(self, max_n: int = 4):
        self.max_n = int(max_n)
        self._idx: Dict[int, _NGramIndex] = {}     # rid -> index
        self._lock = threading.Lock()
        # host-side lookups, but a "draft step" all the same: one propose()
        # per engine verify, so draft-vs-verify counts stay comparable
        self.draft_calls = 0

    def on_join(self, req) -> None:
        with self._lock:
            self._idx[req.rid] = _NGramIndex(
                list(req.prompt) + list(req.output_tokens), self.max_n)

    def propose(self, active, k):
        with self._lock:
            self.draft_calls += 1
            out = {}
            for s, r in active.items():
                idx = self._idx.get(r.rid)
                if idx is None:   # defensive: late registration costs O(len)
                    idx = _NGramIndex(
                        list(r.prompt) + list(r.output_tokens), self.max_n)
                    self._idx[r.rid] = idx
                out[s] = idx.propose(k)
            return out

    def observe(self, req, n_accepted: int) -> None:
        with self._lock:
            idx = self._idx.get(req.rid)
            if idx is not None and n_accepted > 0:
                idx.extend(req.output_tokens[-n_accepted:])

    def on_evict(self, req) -> None:
        with self._lock:
            self._idx.pop(req.rid, None)

    def info(self) -> dict:
        return {"kind": self.kind, "max_n": self.max_n,
                "draft_calls": self.draft_calls}


class DraftModelDrafter(Drafter):
    """Shrunk-config draft model over the engine's batch-slot layout.

    The draft keeps its own per-layer KV caches [max_batch, S_max, ...]
    and a per-request cursor ``draft_len`` = number of cache positions
    holding K/V of the TRUE token stream. Each propose() first catches
    the cursor up by feeding the true tokens the target accepted since
    last round (positions the draft mispredicted are simply overwritten),
    then rolls the draft forward k tokens greedily. All feeds are batched
    [B, 1] calls through the draft model's own captured slot step —
    propose() costs ``max(catch_up) + k - 1`` draft calls per engine
    step, amortized over every active slot."""

    kind = "model"

    def __init__(self, draft_model, max_batch: int, max_seq_len: int):
        import jax.numpy as jnp

        self.model = draft_model
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self._params = [p._value for p in draft_model.parameters()]
        self._caches = [(kc._value, vc._value) for kc, vc in
                        draft_model.init_kv_caches(self.max_batch,
                                                   self.max_seq_len)]
        self._cache_shape = self._caches[0][0].shape[1:]
        self._cache_dtype = self._caches[0][0].dtype
        self._step_fn = compiled_step(draft_model, "slot")
        self._jnp = jnp
        self._draft_len: Dict[int, int] = {}       # rid -> valid positions
        self._last_k = 0                           # window of the last propose
        self.draft_calls = 0

    # The engine's bucketed batch-1 prefill, replayed on the draft weights.
    # The bucket ladder here is DELIBERATELY independent of the engine's
    # configurable prefill buckets: padding is invariant for the draft
    # (masked positions never enter its cache), and a fixed ladder keeps
    # the drafter usable standalone — it only costs draft-side lowerings,
    # never tokens.
    def on_join(self, req) -> None:
        jnp = self._jnp
        from .engine import _write_slot
        plen = req.prompt.size
        bucket = 8
        while bucket < plen:
            bucket *= 2
        bucket = min(bucket, self.max_seq_len)
        tok = np.zeros((1, bucket), np.int64)
        tok[0, :plen] = req.prompt
        pref = [(jnp.zeros((1,) + self._cache_shape, self._cache_dtype),
                 jnp.zeros((1,) + self._cache_shape, self._cache_dtype))
                for _ in self._caches]
        _, pref_out = self._step_fn(
            self._params, jnp.asarray(tok), pref,
            jnp.zeros((1,), jnp.int32),
            jnp.asarray([plen - 1], jnp.int32))
        self._caches = _write_slot(self._caches, pref_out,
                                   jnp.asarray(req.slot, jnp.int32))
        self._draft_len[req.rid] = plen
        self.draft_calls += 1

    def propose(self, active, k):
        jnp = self._jnp
        b = self.max_batch
        self._last_k = int(k)
        feeds: Dict[int, List[int]] = {}
        for s, r in active.items():
            stream = list(r.prompt) + list(r.output_tokens)
            dl = self._draft_len.get(r.rid, r.cache_len)
            # true tokens not yet in the draft cache, ending at the
            # pending token (stream[cache_len], not yet fed anywhere)
            feeds[s] = [int(t) for t in stream[dl:r.cache_len + 1]]
        rounds = max(len(f) for f in feeds.values()) + k - 1
        drafts: Dict[int, List[int]] = {s: [] for s in feeds}
        last = {s: feeds[s][0] for s in feeds}
        for r_i in range(rounds):
            tok = np.zeros((b, 1), np.int64)
            off = np.zeros((b,), np.int32)
            for s, r in active.items():
                f = feeds[s]
                fed = f[r_i] if r_i < len(f) else last[s]
                tok[s, 0] = fed
                dl = self._draft_len.get(r.rid, r.cache_len)
                off[s] = min(dl + r_i, self.max_seq_len - 1)
            nxt, self._caches = self._step_fn(
                self._params, jnp.asarray(tok), self._caches,
                jnp.asarray(off), np.zeros((b,), np.int32))
            self.draft_calls += 1
            out = np.asarray(nxt)
            for s in feeds:
                if r_i >= len(feeds[s]) - 1 and len(drafts[s]) < k:
                    drafts[s].append(int(out[s]))
                    last[s] = int(out[s])
        return drafts

    def observe(self, req, n_accepted: int) -> None:
        # Positions fed with true tokens + ACCEPTED-AND-FED proposals are
        # valid. propose() feeds proposals 1..k-1 only (the k-th is
        # generated last and never written), so on a full-window accept
        # (n_accepted == k+1) the valid prefix ends at old+k-1, not
        # old+k — without the k-1 cap the cursor would skip one stream
        # position forever and every later draft forward would attend a
        # never-written KV row. cache_len is already advanced, recompute.
        old = req.cache_len - n_accepted
        fed_drafts = min(max(0, n_accepted - 1), max(0, self._last_k - 1))
        self._draft_len[req.rid] = min(old + 1 + fed_drafts, req.cache_len,
                                       self.max_seq_len - 1)

    def on_evict(self, req) -> None:
        self._draft_len.pop(req.rid, None)

    def info(self) -> dict:
        cfg = self.model.config
        return {"kind": self.kind, "draft_calls": self.draft_calls,
                "draft_config": {"hidden": cfg.hidden_size,
                                 "layers": cfg.num_hidden_layers}}


def build_drafter(spec, max_batch: int, max_seq_len: int,
                  draft_model=None) -> Optional[Drafter]:
    """Resolve the engine's drafter knob: a Drafter instance passes
    through; "ngram" (default) needs nothing; "model" needs the
    ``draft_model`` the engine was given."""
    if spec is None or isinstance(spec, Drafter):
        return spec
    name = str(spec).lower()
    if name == "ngram":
        return NGramDrafter()
    if name == "model":
        if draft_model is None:
            raise ValueError(
                "drafter='model' needs a draft_model (a shrunk-config "
                "model of the target family) passed to the engine")
        return DraftModelDrafter(draft_model, max_batch, max_seq_len)
    raise ValueError(f"unknown drafter {spec!r} (ngram | model | a "
                     f"Drafter instance)")
