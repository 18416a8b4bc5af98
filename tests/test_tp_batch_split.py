"""The tensor-parallel layers keep the batch split over 'dp'.

A layer constrains only the dim it owns (features, vocab, or the sequence
under sequence parallelism); the batch is left to sharding propagation.  On
a dp x mp mesh every device then runs the block's matmuls over its own
B/dp sequences, and no activation is all-gathered over 'dp'.  These tests
read the partitioned HLO one device runs, at sizes chosen so that the
global and the local token counts collide with no weight dim.
"""
import re

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     build_hybrid_train_step)
from paddle_tpu.parallel import mesh as mesh_mod

B, S, H = 6, 40, 64          # global batch 6 -> 3 a dp replica
DP, MP = 2, 2


@pytest.fixture(autouse=True)
def _no_mesh():
    mesh_mod.set_mesh(None)
    yield
    mesh_mod.set_mesh(None)


def _shapes(text):
    """Dims of every array shape in an HLO fragment."""
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\b(?:f32|bf16|pred|s32)\[([\d,]*)\]", text)]


def _results(hlo, op):
    """Result shapes of every instruction `op` (tuple results flattened)."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) " + re.escape(op)
                     + r"(?:-start)?\(", line)
        if m:
            out.append(_shapes(m.group(1)))
    return out


@pytest.mark.parametrize("sequence_parallel", [False, True],
                         ids=["tp", "tp_sp"])
def test_hybrid_step_dp_mp_keeps_batch_local(sequence_parallel):
    cfg = LlamaConfig.tiny(vocab=72, hidden=H, layers=2, heads=4, inter=96,
                           seq=S)
    cfg.sequence_parallel = sequence_parallel
    rng = np.random.RandomState(5)
    ids = rng.randint(0, cfg.vocab_size, (B, S + 1))
    batch = {"input_ids": P.to_tensor(ids[:, :-1]),
             "labels": P.to_tensor(ids[:, 1:])}

    P.seed(17)
    model = LlamaForCausalLM(cfg)
    ref_loss = float(model.compute_loss(batch["input_ids"],
                                        batch["labels"]).numpy())

    mesh_mod.init_mesh({"dp": DP, "mp": MP})
    opt = P.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = build_hybrid_train_step(model, opt)
    hlo = step.compiled(batch).as_text()

    # every matmul runs over this replica's token rows, never the global
    # batch's: [B/dp * S, ...] flattened, or [B/dp, heads, S, ...]
    dots = [s for op in ("dot", "convolution") for r in _results(hlo, op)
            for s in r]
    assert dots
    assert not [s for s in dots if B * S in s], dots
    assert not [s for s in dots if len(s) >= 3 and s[0] == B], dots
    local_rows = [s for s in dots if B // DP * S in s]
    attn = [s for s in dots if len(s) == 4 and s[:2] == (B // DP, 4 // MP)]
    assert len(local_rows) >= 6 * cfg.num_hidden_layers, dots
    assert attn, dots

    # no activation is all-gathered back to the global batch
    gathered = [s for r in _results(hlo, "all-gather") for s in r]
    assert not [s for s in gathered if (len(s) >= 2 and s[0] == B)
                or B * S in s], gathered

    # between blocks (the layer scan's carry) an activation is this
    # replica's sequences, and under sequence parallelism its sequence
    # slice over mp
    seq = S // MP if sequence_parallel else S
    carries = {s for r in _results(hlo, "while") for s in r
               if len(s) == 3 and s[-1] == H and s[0] != cfg.num_hidden_layers}
    assert carries == {(B // DP, seq, H)}, carries

    loss = float(step(batch).numpy())
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "vpp"])
def test_pipeline_microbatches_split_rows_over_dp(schedule):
    """dp2 x pp2 x mp2 under sequence parallelism: each microbatch's rows
    lie over 'dp'.  A bare reshape of the dp-split batch hands each replica
    whole microbatches, and the stages then reshard them by
    collective-permutes inside the schedule's stage-dependent conditionals,
    where the other stage's devices never arrive.  Inside the schedule the
    only permutes left are its own hand-offs, and the loss is the mesh-less
    one."""
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=4, heads=4, inter=64)
    cfg.sequence_parallel = True
    rng = np.random.RandomState(9)
    ids = rng.randint(0, cfg.vocab_size, (8, 17))
    batch = {"input_ids": P.to_tensor(ids[:, :-1]),
             "labels": P.to_tensor(ids[:, 1:])}

    P.seed(23)
    model = LlamaForCausalLM(cfg)
    ref_loss = float(model.compute_loss(batch["input_ids"],
                                        batch["labels"]).numpy())

    mesh_mod.init_mesh({"dp": 2, "pp": 2, "mp": 2})
    opt = P.optimizer.SGD(learning_rate=0.05, parameters=model.parameters())
    step = build_hybrid_train_step(model, opt, n_microbatches=4,
                                   schedule=schedule,
                                   n_virtual=2 if schedule == "vpp" else 1)
    hlo = step.compiled(batch).as_text()
    permutes = [line.strip() for line in hlo.splitlines()
                if re.search(r" collective-permute(-start)?\(", line)
                and "/shard_map/" in line]
    assert permutes
    assert all('ppermute"' in p for p in permutes), permutes

    loss = float(step(batch).numpy())
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5, atol=1e-6)
