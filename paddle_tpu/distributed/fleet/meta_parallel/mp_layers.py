"""Tensor-parallel layers.

Analog of python/paddle/distributed/fleet/layers/mpu/mp_layers.py
(VocabParallelEmbedding:44, ColumnParallelLinear:312, RowParallelLinear:516,
ParallelCrossEntropy:713).

TPU-native design: instead of manually slicing weights per rank and wiring
c_identity/c_allreduce collectives, each layer declares a PARTITION SPEC on its
weight and places GSPMD sharding constraints on activations. XLA's SPMD
partitioner then inserts exactly the all-reduce/all-gather the reference codes
by hand — and fuses/overlaps them with the matmuls on ICI.

A constraint states only the dim the layer owns (features, vocab, or the
sequence under sequence parallelism).  Every other dim is left to sharding
propagation: pinned replicated, the batch would be gathered over 'dp' into
each layer, and every dp replica would repeat the others' matmuls.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ....core.tensor import Parameter
from ....nn import functional as F
from ....nn.initializer import Constant, XavierNormal
from ....nn.layer.layers import Layer
from ....ops.dispatch import apply
from ....parallel.mesh import mesh_axis_size, shard_constraint

MP_AXIS = "mp"


def owned_spec(ndim, dim, axis):
    """Spec of an ndim activation whose dim ``dim`` lies on mesh ``axis``
    (None: replicated), every other dim left to propagation."""
    spec = [PartitionSpec.UNCONSTRAINED] * ndim
    spec[dim] = axis
    return spec


class VocabParallelEmbedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter([num_embeddings, embedding_dim],
                                            attr=weight_attr)
        XavierNormal()(self.weight)
        self.weight._sharding = (MP_AXIS, None)  # vocab dim split across mp
        self.weight.is_distributed = mesh_axis_size(MP_AXIS) > 1

    def forward(self, x):
        out = F.embedding(x, self.weight)  # weight sharding rides the param spec
        return shard_constraint_t(out, *owned_spec(len(out.shape), -1, None))


def shard_constraint_t(tensor, *spec):
    """Apply a GSPMD constraint to a Tensor (autograd-transparent)."""
    return apply(lambda v: shard_constraint(v, *spec), tensor,
                 op_name="shard_constraint")


class ColumnParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter([in_features, out_features],
                                            attr=weight_attr)
        XavierNormal()(self.weight)
        self.weight._sharding = (None, MP_AXIS)  # split output columns
        self.weight.is_distributed = mesh_axis_size(MP_AXIS) > 1
        if has_bias:
            self.bias = self.create_parameter([out_features], is_bias=True)
            self.bias._sharding = (MP_AXIS,)
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        # gathered: the output features whole on every mp rank; else they
        # stay split across mp
        axis = None if self.gather_output else MP_AXIS
        return shard_constraint_t(out, *owned_spec(len(out.shape), -1, axis))


class RowParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=False, fuse_matmul_bias=False, mp_group=None,
                 name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter([in_features, out_features],
                                            attr=weight_attr)
        XavierNormal()(self.weight)
        self.weight._sharding = (MP_AXIS, None)  # split input rows
        self.weight.is_distributed = mesh_axis_size(MP_AXIS) > 1
        self.bias = self.create_parameter([out_features], is_bias=True) \
            if has_bias else None

    def forward(self, x):
        if self.input_is_parallel:
            x = shard_constraint_t(x, *owned_spec(len(x.shape), -1, MP_AXIS))
        # contraction over the sharded dim => XLA inserts the all-reduce the
        # reference codes as c_allreduce_sum (mp_ops.py _mp_allreduce)
        out = F.linear(x, self.weight, None)
        out = shard_constraint_t(out, *owned_spec(len(out.shape), -1, None))
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(Layer):
    """CE over mp-sharded logits (mp_layers.py:713). With GSPMD the softmax
    reduction over the sharded class dim lowers to the same all-reduce pair
    the reference implements manually (c_softmax_with_cross_entropy)."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        logits = shard_constraint_t(
            input, *owned_spec(len(input.shape), -1, MP_AXIS))
        return F.cross_entropy(logits, label, reduction="none",
                               ignore_index=self.ignore_index)


# ---------------- Megatron-style sequence parallel ----------------
# (fleet/utils/sequence_parallel_utils.py:83-145,228,340)

class ScatterOp:
    """Split activations along seq dim over mp — on TPU a sharding constraint."""

    @staticmethod
    def apply(x, axis=1):
        return shard_constraint_t(x, *owned_spec(len(x.shape), axis, MP_AXIS))


class GatherOp:
    @staticmethod
    def apply(x, axis=1):
        return shard_constraint_t(x, *owned_spec(len(x.shape), axis, None))


class AllGatherOp(GatherOp):
    pass


class ReduceScatterOp:
    @staticmethod
    def apply(x, axis=1):
        return shard_constraint_t(x, *owned_spec(len(x.shape), axis, MP_AXIS))


def mark_as_sequence_parallel_parameter(param):
    param.is_sequence_parallel = True


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse=False):
    """No-op on TPU: the grad all-reduce for sequence-parallel params is
    inserted by XLA from the sharding specs (reference needs explicit hooks,
    sequence_parallel_utils.py:190)."""
    return model


class ColumnSequenceParallelLinear(ColumnParallelLinear):
    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 gather_output=False, fuse_matmul_bias=False, mp_group=None,
                 name=None):
        super().__init__(in_features, out_features, weight_attr, has_bias,
                         gather_output, fuse_matmul_bias, mp_group, name)

    def forward(self, x):
        # input arrives seq-sharded; all-gather over seq happens inside the
        # partitioner as part of the matmul
        x = GatherOp.apply(x)
        return super().forward(x)


class RowSequenceParallelLinear(RowParallelLinear):
    def __init__(self, in_features, out_features, weight_attr=None, has_bias=True,
                 input_is_parallel=True, fuse_matmul_bias=False, mp_group=None,
                 name=None):
        super().__init__(in_features, out_features, weight_attr, has_bias,
                         input_is_parallel, fuse_matmul_bias, mp_group, name)

    def forward(self, x):
        out = super().forward(x)
        return ScatterOp.apply(out)  # reduce-scatter back to seq shards
