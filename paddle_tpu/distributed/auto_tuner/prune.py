"""Prune rules (reference: python/paddle/distributed/auto_tuner/prune.py —
register_prune:39, prune_by_mp:48, prune_by_pp:85, prune_by_mbs:116,
prune_by_num_gpus:270).

A rule returns True when the candidate config should be SKIPPED. Rules get
(tuner_cfg, cur_cfg, history) — history entries are dicts with the measured
metric (or an error marker) so rules can also prune from past failures
(e.g. OOM at a smaller micro-batch count)."""
from __future__ import annotations

_PRUNE_FNS = []


def register_prune(fn):
    _PRUNE_FNS.append(fn)
    return fn


def same_cfgs_beside(attr, cur_cfg, history):
    """History entries equal to cur_cfg except for `attr`."""
    out = []
    for h in history:
        cfg = h["cfg"]
        if all(cfg.get(k) == v for k, v in cur_cfg.items() if k != attr):
            out.append(h)
    return out


@register_prune
def prune_by_num_devices(tuner_cfg, cur, history=None):
    n = tuner_cfg.get("num_devices")
    if n is None:
        return False
    degree = (cur.get("dp_degree", 1) * cur.get("mp_degree", 1)
              * cur.get("pp_degree", 1) * cur.get("sharding_degree", 1))
    return degree != n


@register_prune
def prune_by_mp(tuner_cfg, cur, history=None):
    mp = cur.get("mp_degree", 1)
    heads = tuner_cfg.get("num_attention_heads")
    vocab = tuner_cfg.get("vocab_size")
    hidden = tuner_cfg.get("hidden_size")
    if heads and heads % mp != 0:
        return True
    if vocab and vocab % mp != 0:
        return True
    if hidden and hidden % mp != 0:
        return True
    return False


@register_prune
def prune_by_pp(tuner_cfg, cur, history=None):
    pp = cur.get("pp_degree", 1)
    layers = tuner_cfg.get("num_layers")
    if layers and layers % pp != 0:
        return True
    if pp > 1 and cur.get("micro_batches", 1) % pp != 0 \
            and cur.get("schedule", "gpipe") == "vpp":
        return True
    return False


@register_prune
def prune_by_mbs(tuner_cfg, cur, history=None):
    """global batch must divide into dp x micro_batches."""
    gbs = tuner_cfg.get("global_batch_size")
    if not gbs:
        return False
    dp = cur.get("dp_degree", 1)
    mb = cur.get("micro_batches", 1)
    if gbs % (dp * mb) != 0:
        return True
    return False


def _state_bytes(n_params, cur):
    """Per-device parameter-state bytes: 4B master + 8B adam moments sharded
    over mp*pp*sharding, plus the 2B bf16 compute copy sharded over mp*pp.
    Single source of truth for every memory-based prune rule."""
    mp = cur.get("mp_degree", 1)
    pp = cur.get("pp_degree", 1)
    sh = cur.get("sharding_degree", 1)
    return (n_params * (4 + 8) / (mp * pp * max(sh, 1))
            + n_params * 2 / (mp * pp))


@register_prune
def prune_by_memory_estimate(tuner_cfg, cur, history=None):
    """Rough HBM estimate: params(4B master + 8B adam + 2B compute copy) /
    (mp*pp*sharding) + activations/(dp*mp). Skip when over budget."""
    budget = tuner_cfg.get("hbm_bytes")
    n_params = tuner_cfg.get("num_params")
    if not budget or not n_params:
        return False
    mp = cur.get("mp_degree", 1)
    pp = cur.get("pp_degree", 1)
    state_and_compute = _state_bytes(n_params, cur)
    gbs = tuner_cfg.get("global_batch_size", 1)
    seq = tuner_cfg.get("seq_length", 1)
    hidden = tuner_cfg.get("hidden_size", 1)
    layers = tuner_cfg.get("num_layers", 1)
    dp = cur.get("dp_degree", 1)
    mb = cur.get("micro_batches", 1)
    act = 2.0 * gbs / dp / mb * seq * hidden * layers / pp / mp
    if not cur.get("use_recompute", False):
        act *= 4.0
    return (state_and_compute + act) > budget


@register_prune
def prune_by_schedule_tradeoff(tuner_cfg, cur, history=None):
    """Schedule choice from the schedules' tradeoff: the fused-round 1F1B
    runs M + 2(pp-1) rounds with no dispatch branch in steady state and
    stashes min(2*pp-1, M) microbatch activations vs gpipe's M+pp-1 (it was
    also the faster one wherever both were timed, which was a virtual CPU
    mesh only) — gpipe is dominated whenever a pipeline exists, so it is
    pruned at pp>1; 1f1b machinery is pure cost at pp<=1.
    Applies only to candidates that explicitly carry a schedule choice."""
    schedule = cur.get("schedule")
    if schedule not in ("gpipe", "1f1b"):
        return False
    pp = cur.get("pp_degree", 1)
    if pp <= 1:
        return schedule == "1f1b"  # no pipeline, 1f1b machinery is pure cost
    return schedule == "gpipe"     # dominated: slower AND bigger stash


@register_prune
def prune_by_history_error(tuner_cfg, cur, history=None):
    """If the same config modulo micro_batches OOMed with FEWER micro-batches,
    a config with even fewer will OOM too (larger per-step activations)."""
    if not history:
        return False
    for h in same_cfgs_beside("micro_batches", cur, history):
        if h.get("error") == "oom" and \
                cur.get("micro_batches", 1) < h["cfg"].get("micro_batches", 1):
            return True
    return False


def prune(tuner_cfg, cur, history):
    return any(fn(tuner_cfg, cur, history) for fn in _PRUNE_FNS)
