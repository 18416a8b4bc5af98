"""Sharded embedding tables (distributed/embedding/).

The exactness ladder under test:

1. dp1 / no mesh: ShardedEmbedding is BITWISE the dense nn.Embedding
   reference — same initializer draws, same jnp.take gather;
2. dp2 proxy (virtual CPU devices): the unique -> id all_to_all ->
   gather -> wire-return exchange is bitwise the dense gather with the
   quantized context off (forward AND gradients), and within the
   blockwise wire error bound with it on;
3. the whole DeepFM train step captures over the exchange, lowers once,
   lints clean, and its dp2 loss curve is bitwise the dp1 curve;
4. the wire legs are routed through distributed/comms (CommOp records,
   compression accounting) — no naked collectives;
5. a row-sharded table spec plans through plan_reshard and a scale event
   (grow/shrink) rides the PR 8 redistribute executor bitwise.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.distributed import comms
from paddle_tpu.distributed.embedding import (ShardedEmbedding, hash_bucket,
                                              sharded_lookup,
                                              table_param_spec)
from paddle_tpu.models import DeepFM
from paddle_tpu.nn.layer.common import Embedding
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.parallel.trainer import compile_train_step


@pytest.fixture(autouse=True)
def _clean_mesh_and_comms():
    prev = mesh_mod.get_mesh()
    comms.comm_clear()
    yield
    mesh_mod.set_mesh(prev)
    comms.comm_clear()


def _unwrap(x):
    return x._value if hasattr(x, "_value") else x


def _dp_mesh(n):
    return mesh_mod.init_mesh({"dp": n}, devices=jax.devices()[:n])


# ---------------- hash bucketing ----------------

def test_hash_bucket_identity_and_hashed():
    ids = jnp.asarray([0, 1, 31, 63])
    # identity-mod: in-range ids keep their row (the dp1-bitwise contract)
    np.testing.assert_array_equal(np.asarray(hash_bucket(ids, 64, False)),
                                  [0, 1, 31, 63])
    b = np.asarray(hash_bucket(ids, 64, True))
    assert b.dtype == np.int32 and np.all((0 <= b) & (b < 64))
    # deterministic, and it actually mixes (not the identity)
    b2 = np.asarray(hash_bucket(ids, 64, True))
    np.testing.assert_array_equal(b, b2)
    assert not np.array_equal(b, np.asarray(ids))


def test_hash_bucket_spreads_arbitrary_id_space():
    # 100k-scale raw ids land roughly uniformly over the buckets
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 10**8, 4096))
    counts = np.bincount(np.asarray(hash_bucket(ids, 16, True)), minlength=16)
    assert counts.min() > 0.5 * 4096 / 16, counts


# ---------------- dp1: bitwise the dense reference ----------------

def test_dp1_bitwise_dense_reference():
    mesh_mod.set_mesh(None)
    P.seed(11)
    sharded = ShardedEmbedding(32, 8)
    P.seed(11)
    dense = Embedding(32, 8)
    np.testing.assert_array_equal(np.asarray(sharded.weight._value),
                                  np.asarray(dense.weight._value))
    ids = P.to_tensor(np.random.RandomState(0).randint(0, 32, (6, 4)))
    np.testing.assert_array_equal(np.asarray(_unwrap(sharded(ids))),
                                  np.asarray(_unwrap(dense(ids))))


def test_indivisible_table_degrades_to_dense_bitwise():
    # 33 rows on dp2: the exchange path refuses (rows % n != 0) and the
    # dense gather serves — correctness never depends on the fast path
    _dp_mesh(2)
    w = jnp.asarray(np.random.RandomState(1).randn(33, 4).astype(np.float32))
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 33, (8, 3)))
    out = _unwrap(sharded_lookup(ids, w))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.take(w, ids.astype(jnp.int32),
                                             axis=0)))


# ---------------- dp2: the exchange path ----------------

def _rand_table(rows=32, dim=8, seed=7):
    return jnp.asarray(np.random.RandomState(seed).randn(rows, dim)
                       .astype(np.float32))


def test_dp2_lookup_bitwise_and_sites_recorded():
    _dp_mesh(2)
    w = _rand_table()
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 32, (8, 4)))
    out = np.asarray(_unwrap(sharded_lookup(ids, w)))
    ref = np.asarray(jnp.take(w, ids.astype(jnp.int32), axis=0))
    np.testing.assert_array_equal(out, ref)
    sites = comms.comm_info()["sites"]
    assert "embedding.ids/all_to_all/dp" in sites
    assert "embedding.rows/all_to_all/dp" in sites
    # exact regime: wire == logical (nothing flattered)
    rows = sites["embedding.rows/all_to_all/dp"]
    assert rows["bytes_wire"] == rows["bytes_logical"] > 0


def test_dp2_grad_bitwise_dense_reference():
    _dp_mesh(2)
    w = _rand_table()
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 32, (8, 4)))
    scale = jnp.arange(8.0)

    def loss_sharded(ww):
        return jnp.sum(jnp.tanh(_unwrap(sharded_lookup(ids, ww))) * scale)

    def loss_dense(ww):
        return jnp.sum(jnp.tanh(jnp.take(ww, ids.astype(jnp.int32), axis=0))
                       * scale)

    gs = np.asarray(jax.grad(loss_sharded)(w))
    gd = np.asarray(jax.grad(loss_dense)(w))
    # duplicates included: the dedup'd push pre-accumulates per unique id,
    # and the result still lands bitwise on this proxy
    np.testing.assert_array_equal(gs, gd)


@pytest.mark.parametrize("rows,dim,batch,fields,floor", [
    (32, 8, 8, 4, 1.0),
    # a table and a batch wide enough that per-block scales and padding are
    # small beside the payload: the row leg's acceptance floor
    (4096, 64, 256, 8, 3.5)], ids=["tiny", "wide"])
def test_dp2_quantized_lookup_and_grad_within_wire_error_bound(
        rows, dim, batch, fields, floor):
    _dp_mesh(2)
    w = _rand_table(rows, dim)
    ids = jnp.asarray(
        np.random.RandomState(5).randint(0, rows, (batch, fields)))
    ref = np.asarray(jnp.take(w, ids.astype(jnp.int32), axis=0))

    # ONE value_and_grad trace serves both halves (grad-of-shard_map
    # compiles dominate this file's wall clock)
    def run(ww):
        out = _unwrap(sharded_lookup(ids, ww))
        return jnp.sum(jnp.tanh(out)), out

    (_, out_d), gd = jax.value_and_grad(run, has_aux=True)(w)
    comms.comm_clear()
    with comms.quantized("int8"):
        (_, out_q), gq = jax.value_and_grad(run, has_aux=True)(w)
    out_q, gq, gd = np.asarray(out_q), np.asarray(gq), np.asarray(gd)
    np.testing.assert_array_equal(np.asarray(out_d), ref)  # off: bitwise
    # blockwise int8: |err| <= block absmax / 254 <= global absmax / 254
    bound = np.abs(np.asarray(w)).max() / 254 + 1e-6
    assert np.max(np.abs(out_q - ref)) <= bound
    # straight-through gradient on the wire: finite and close, not bitwise
    assert np.all(np.isfinite(gq))
    assert np.max(np.abs(gq - gd)) <= 0.1 * (np.abs(gd).max() + 1.0)
    sites = comms.comm_info()["sites"]
    row_leg = sites["embedding.rows/all_to_all/dp"]
    assert row_leg["quantized"] == "int8"
    assert 0 < row_leg["bytes_wire"] < row_leg["bytes_logical"]
    assert row_leg["bytes_logical"] / row_leg["bytes_wire"] >= floor, row_leg
    # id legs stay exact int32; the sparse grad push crossed the wire
    assert sites["embedding.ids/all_to_all/dp"]["quantized"] is None
    assert "embedding.rows.grad/all_to_all/dp" in sites


def test_capacity_overflow_drops_to_zero_embedding():
    _dp_mesh(2)
    w = _rand_table(rows=4, dim=2)
    # per rank: two distinct ids, both owned by shard 0 -> capacity 1
    # keeps the smaller unique (ids sort first), drops the other to the
    # documented zero embedding (the MoE capacity-factor semantics)
    ids = jnp.asarray([[0, 1], [0, 1]])
    out = np.asarray(_unwrap(sharded_lookup(ids, w, capacity=1)))
    ref = np.asarray(jnp.take(w, ids.astype(jnp.int32), axis=0))
    np.testing.assert_array_equal(out[:, 0], ref[:, 0])        # kept
    np.testing.assert_array_equal(out[:, 1], np.zeros((2, 2)))  # dropped


# ---------------- DeepFM end-to-end through the captured step ----------------

def _tiny_deepfm(seed=0):
    P.seed(seed)
    model = DeepFM(sparse_feature_number=64, sparse_feature_dim=8,
                   dense_feature_dim=4, sparse_field_num=6,
                   layer_sizes=(16,))
    opt = P.optimizer.SGD(learning_rate=0.05,
                          parameters=model.parameters())
    return model, opt


def _ctr_loss(m, b):
    return nn.functional.binary_cross_entropy_with_logits(
        m(b["sparse"], b["dense"]), b["y"])


def _ctr_batch(B=32, seed=0):
    rng = np.random.RandomState(seed)
    return {"sparse": P.to_tensor(rng.randint(0, 64, (B, 6))),
            "dense": P.to_tensor(rng.randn(B, 4).astype(np.float32)),
            "y": P.to_tensor((rng.rand(B, 1) > 0.5).astype(np.float32))}


def _drive(mesh_n, steps=3, quant=False):
    if mesh_n > 1:
        mesh = _dp_mesh(mesh_n)
    else:
        mesh_mod.set_mesh(None)
        mesh = None
    model, opt = _tiny_deepfm()
    step = compile_train_step(model, _ctr_loss, opt, mesh=mesh)

    def run():
        return [float(step(_ctr_batch()).numpy()) for _ in range(steps)]

    if quant:
        with comms.quantized("int8"):
            losses = run()
    else:
        losses = run()
    return losses, step


def test_deepfm_captured_step_dp2_bitwise_dp1_and_quantized_parity():
    # ONE dp2 exact run is the anchor for both halves (train-step builds
    # dominate this file's wall clock — don't build it twice)
    l2, step2 = _drive(2)
    l1, _ = _drive(1)
    assert l2 == l1, (l2, l1)
    assert l2[-1] < l2[0]
    # one lowering, exchange collectives tagged by the comm pass
    assert step2.captured_program is not None
    rep = step2.captured_program.pass_report
    assert rep.comm_tagged >= 4, rep.as_dict()   # >=2 tables x 2 wire legs
    # the captured step lints clean — the same program the staticcheck
    # jaxpr tier gates (zero unscheduled collectives, no dead compute)
    from paddle_tpu.jit.passes import lint
    rec = lint.lint_records().get("pure_step")
    assert rec is not None and rec["findings"] == [], rec

    # quantized regime: finite, loss-parity vs the exact curve, and both
    # the embedding combine and the grad sync ride the int8 wire
    comms.comm_clear()
    lq, _ = _drive(2, quant=True)
    assert np.isfinite(lq[-1])
    assert abs(lq[-1] - l2[-1]) / max(abs(l2[-1]), 1e-9) < 0.1, (lq, l2)
    sites = comms.comm_info()["sites"]
    assert sites["embedding.rows/all_to_all/dp"]["quantized"] == "int8"
    assert sites["trainer.grad_sync/all_reduce/dp"]["quantized"] == "int8"


# ---------------- scale events ride the PR 8 executor ----------------

def test_row_sharded_table_reshard_grow_and_shrink():
    from paddle_tpu.distributed import reshard as rs

    rows, dim = 16, 4
    full = np.arange(rows * dim, dtype=np.float32).reshape(rows, dim)

    # grow: 2 owners -> 4 owners, rows stay sharded on the same axis
    src = rs.MeshSpec.from_members(["a", "b"], shape={"mp": 2})
    dst = rs.MeshSpec.from_members(["a", "b", "c", "d"], shape={"mp": 4})
    spec = table_param_spec(rows, dim, src_axis="mp", dst_axis="mp")
    plan = rs.plan_reshard(src, dst, {"table": spec})
    assert plan.recoverable_from_peers
    assert plan.bytes_moved > 0
    states = {"a": {"table": full[:8].copy()}, "b": {"table": full[8:].copy()}}
    out, _ = rs.redistribute(src, dst, {"table": spec}, states)
    for i, o in enumerate(["a", "b", "c", "d"]):
        np.testing.assert_array_equal(out[o]["table"], full[i * 4:(i + 1) * 4])

    # shrink back 4 -> 2 with one owner dead: survivors supply the bricks
    back = rs.plan_reshard(dst, src, {"table": spec},
                           available={"a", "b", "c"})
    # owner 'd' held rows 12..16, which nobody else holds
    assert not back.recoverable_from_peers
    lost_rows = {p.index[0] for p in back.lost}
    assert lost_rows == {(12, 16)}


def test_table_reshard_replicate_to_sharded():
    """An embedding table trained replicated (dp1 job) scale-events onto a
    row-sharded mesh: src spec None, dst spec mp — the planner reuses the
    local copy where possible and ships only the missing rows."""
    from paddle_tpu.distributed import reshard as rs

    rows, dim = 8, 2
    full = (np.arange(rows * dim, dtype=np.float32) + 1).reshape(rows, dim)
    src = rs.MeshSpec.from_members(["a"], shape={"mp": 1})
    dst = rs.MeshSpec.from_members(["a", "b"], shape={"mp": 2})
    spec = table_param_spec(rows, dim, src_axis=None, dst_axis="mp")
    out, plan = rs.redistribute(src, dst, {"table": spec},
                                {"a": {"table": full.copy()}})
    np.testing.assert_array_equal(out["a"]["table"], full[:4])
    np.testing.assert_array_equal(out["b"]["table"], full[4:])
    # the owner that already held everything reused its bytes locally
    assert plan.bytes_local > 0
