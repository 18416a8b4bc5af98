"""ops/pallas/grouped_expert_matmul.py: the routed experts' two matmuls (gate
and up side by side, then down), twice a chunk of at most 1024 tokens an
expert layer.  Counted from the run's evidence, whatever implements it:

- operations: 6 x hidden x width a LOCAL assignment (three matrices, a
  multiply and an add each).  Local assignments inside the traced window:
  the positions its steps computed (slots a decode step, the bucket a
  prefill, each by its share inside the trace) x top-k x the local share of
  all assignments over the whole window (the engine's `moe_assignments_local`
  over `moe_assignments`);
- bytes: an expert's three matrices once a chunk it has a token in (the
  engine's `moe_experts_hit`, a chunk: the whole window's sum over the
  window's chunks, times the chunks the trace holds, which is half the
  kernel's calls there), and the rows in and out of both matmuls.
"""


def chunks_of(bucket: int, chunk_tokens: int = 1024) -> int:
    return -(-int(bucket) // chunk_tokens)


def steps_inside(ev, lo, hi):
    """(kind, tokens, share inside [lo, hi]) of every engine.prefill and
    engine.decode_step span that overlaps it; the ring's clock is the
    monotonic one (ns)."""
    slots = int(ev["cell"].traffic["slots"])
    out = []
    for sp in ev["spans"]:
        if sp["name"] not in ("engine.prefill", "engine.decode_step") \
                or not sp["dur"]:
            continue
        inside = min(sp["ts"] + sp["dur"], hi) - max(sp["ts"], lo)
        if inside > 0:
            prefill = sp["name"] == "engine.prefill"
            out.append(("prefill" if prefill else "decode",
                        int(sp["args"]["bucket"]) if prefill else slots,
                        inside / sp["dur"]))
    return out


def per_assignment(hidden: int, width: int, itemsize: int = 2):
    """(operations, bytes of rows in and out) one local assignment costs."""
    return 6 * hidden * width, itemsize * (hidden + 2 * width + width + hidden)


def expert_bytes(hidden: int, width: int, itemsize: int = 2) -> int:
    return 3 * hidden * width * itemsize


def work(ev, calls):
    cell, trace, skew = ev["cell"], ev["trace"], ev.get("clock_skew_ns", 0)
    cfg = cell.config
    info = ev.get("engine_info") or {}
    a, b = info.get("before") or {}, info.get("after") or {}
    if "moe_assignments" not in b:
        return {}
    layers = sum(cfg["moe_layer_freq"][:cell.depth()])
    all_ = b["moe_assignments"] - a["moe_assignments"]
    local = b["moe_assignments_local"] - a["moe_assignments_local"]
    hit = b["moe_experts_hit"] - a["moe_experts_hit"]
    window = steps_inside(ev, ev["t0"] * 1e9 + skew, ev["t1"] * 1e9 + skew)
    window_chunks = layers * sum(
        share * (chunks_of(n) if kind == "prefill" else 1)
        for kind, n, share in window)
    if all_ <= 0 or window_chunks <= 0:
        return {}
    traced = steps_inside(ev, trace.t_start * 1e9 + skew,
                          trace.t_stop * 1e9 + skew)
    positions = sum(share * n for _, n, share in traced)
    assignments = layers * positions * cfg["num_experts_per_tok"] \
        * local / all_
    flops_each, rows_each = per_assignment(cfg["hidden_size"],
                                           cfg["moe_intermediate_size"])
    traced_chunks = calls("grouped_expert_matmul") / 2.0
    nbytes = traced_chunks * (hit / window_chunks) * expert_bytes(
        cfg["hidden_size"], cfg["moe_intermediate_size"]) \
        + assignments * rows_each
    return {"grouped_expert_matmul": (assignments * flops_each, nbytes)}
