"""Pallas TPU kernels for the message-passing hot path.

The inner loop of every model is gather -> edge compute (elementwise
weighting, radial envelopes, small edge-MLP/tensor-product GEMMs) ->
dst-sorted segment sum. XLA compiles these as separate HLOs with
materialized ``(E, width)`` intermediates in HBM; the kernels here fuse
the pipeline per tile of DESTINATION nodes instead, exploiting the
repo-wide padding contract (globally nondecreasing ``edge_dst``,
``indices_are_sorted=True`` — ops/segment.py): each dst tile owns a
CONTIGUOUS slice of the edge array, computable with one on-device
``searchsorted`` over the tile boundaries.

Layout:

- :mod:`segment` — the fused gather+scatter segment kernels
  (``pallas_segment_sum``, its in-place form ``pallas_segment_sum_into``
  for the chunks of an edge scan, ``pallas_edge_aggregate``) and the XLA
  reference implementations they are tested against.
- :mod:`so3` — the fused SO(2)/channel-mixing kernel for the MACE/eSCN
  equivariant inner loop (per-|m| complex-pair GEMMs batched into one
  VMEM-resident kernel).
- :mod:`dispatch` — the routing layer every call site goes through:
  Pallas on TPU, pure-XLA everywhere else (or under the
  ``DISTMLIP_KERNELS=0`` kill switch / per-object ``kernels=False``),
  with custom VJPs so ``value_and_grad`` force/stress programs work
  identically on both paths.
"""

from .dispatch import (  # noqa: F401
    Gather,
    KernelCounter,
    Repeat,
    counting,
    force_kernel_mode,
    fused_edge_aggregate,
    fused_segment_sum,
    fused_segment_sum_into,
    fused_so2_conv,
    resolve_kernel_mode,
    segment_sum_carry,
    segment_sum_result,
)
from .segment import (  # noqa: F401
    pallas_edge_aggregate,
    pallas_segment_sum,
    pallas_segment_sum_into,
)
from .so3 import so2_conv_reference  # noqa: F401
