"""Operator library.

The TPU-native equivalent of the reference's ``src/operator/`` (~1000 C++/CUDA
ops registered through NNVM, ref: include/mxnet/op_attr_types.h NNVM_REGISTER_OP)
plus mshadow. Here each operator is a *pure function on jax arrays* registered
in a typed registry (``registry.py``); XLA plays the role of mshadow's
expression compiler and of the cuDNN dispatch layer, and Pallas kernels slot in
for the few genuinely custom kernels. Python-facing namespaces (``mx.nd``,
``mx.sym``) are generated from this registry exactly like the reference
generates them from the C op registry (ref: python/mxnet/ndarray/register.py).
"""
from . import registry
from .registry import register, get, list_ops, Operator, OpParam

# Import op definition modules for their registration side effects, mirroring
# the reference's static registration of src/operator/** at library load.
from . import tensor          # ref: src/operator/tensor/
from . import elemwise        # ref: src/operator/tensor/elemwise_*
from . import nn              # ref: src/operator/nn/
from . import random          # ref: src/operator/random/
from . import optimizer_op    # ref: src/operator/optimizer_op.cc
from . import contrib         # ref: src/operator/contrib/
from . import quantization    # ref: src/operator/quantization/
from . import sequence        # ref: src/operator/sequence_*.cc
from . import ssm             # state-space scan (Mamba-2): no reference analog
from . import moe             # routed experts held here: no reference analog
from . import retention       # power retention (linear attention): no analog
