"""Kernel dispatch: Pallas where it was proven on the chip, XLA elsewhere.

Every fused-kernel call site in the codebase goes through this module,
never through :mod:`segment`/:mod:`so3` directly. The dispatcher owns

- **routing**: trace-time selection of the Pallas kernel vs the
  pure-XLA ops (``ops/segment.py`` semantics). On a TPU backend each op
  takes the mode :data:`TPU_DEFAULT_MODE` records for it; Pallas also
  runs when asked for by name (``kernels="pallas"``), under
  ``DISTMLIP_KERNELS=interpret`` (interpreter-mode kernels — the
  chip-free test lane), or inside a :func:`force_kernel_mode` context;
  the ``DISTMLIP_KERNELS=0`` kill switch and per-object
  ``kernels=False`` force XLA. The decision is static per trace — both
  paths ship from ONE code path with no model forks, and a kernel that
  fails to lower fails the trace: nothing here catches a lowering error.
- **autodiff**: ``pallas_call`` has no transpose rule, so each fused op
  carries a custom VJP. ``fused_segment_sum``'s backward is the sorted
  gather ``g[segment_ids] * mask`` (``fused_segment_sum_into``'s too, the
  carry's cotangent passing through), and ``fused_segment_repeat``, the
  sorted gather, has the kernel's sum for its backward;
  ``fused_edge_aggregate``'s backward
  re-runs the per-edge compute in bounded chunks (a ``lax.scan``) so the
  backward pass ALSO never materializes the ``(E, width)`` message
  cotangent; ``fused_so2_conv``'s backward is the VJP of the XLA
  reference (its operand is already chunk-bounded by the model's edge
  scan). The transposed node-gathers emit unsorted scatter-adds — the
  audited grad-program exemption of the ``scatter_hints`` contract pass.
- **telemetry**: a trace-time counter (:func:`counting`) records how
  many aggregation call sites routed to Pallas vs XLA; the runtime's
  cached contract-audit trace snapshots it into ``StepRecord``'s
  ``kernel_mode``/``kernel_coverage`` fields.
"""

from __future__ import annotations

import math
import os
import threading
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.segment import masked_segment_sum, slab_repeat, slab_sum
from ..telemetry import scope
from .segment import (
    pallas_edge_aggregate,
    pallas_segment_sum,
    pallas_segment_sum_into,
)
from .so3 import (
    packed_m_layout,
    so2_conv_pallas,
    so2_conv_reference,
    wigner_dcols_pallas,
    wigner_rotate_pallas,
    wigner_rotate_reference,
)

# node arrays larger than this are pre-gathered by XLA instead of riding
# VMEM into the kernel for the in-kernel gather
DEFAULT_VMEM_BUDGET = int(os.environ.get("DISTMLIP_KERNELS_VMEM",
                                         2 * 1024 * 1024))
# backward-pass edge chunk (bounds the message-cotangent working set)
DEFAULT_BWD_CHUNK = int(os.environ.get("DISTMLIP_KERNELS_BWD_CHUNK", "32768"))

_MODES = ("pallas", "interpret", "xla")
_local = threading.local()

# What each fused op runs by default on a TPU backend. An op reads
# "pallas" only if it compiled with interpret=False on the chip at a
# published-width shape AND agreed with the XLA path there; the
# measurement is chip_smoke.py's KERNELS phase, which re-takes it on every
# run and fails when it disagrees with this table. An op that Mosaic
# refuses stays "xla" with the compiler's message beside it;
# ``kernels="pallas"`` remains the explicit way to reach it.
TPU_DEFAULT_MODE = {
    # compiled on a TPU v5 lite at MACE's (E_c=32768, nQ=40, 128) scan
    # chunk; max rel err vs XLA float32/highest 1.9e-7 (float32), 3.1e-3
    # (bfloat16: the output's own rounding) — chip run, PR 21
    "segment_sum": "pallas",
    # Mosaic refuses CHGNet's atom-conv message at units = 64 (chip run,
    # PR 21). float32: "Mosaic failed to compile TPU kernel: Slice shape
    # along dimension 2 must be aligned to tiling (128), but is 64" — a
    # 64-wide streamed block is half a lane tile. bfloat16: "'tpu.matmul'
    # op Expected matmul acc to be 32-bit" — the model's `x @ w` inside
    # edge_fn keeps a bf16 result, Mosaic wants preferred_element_type
    # float32. ROADMAP S3 carries both as a perf_opt item.
    "edge_aggregate": "xla",
    # compiled on a TPU v5 lite at eSCN's 128-channel, l_max = 2 SO(2)
    # block over a 32768-edge chunk; max rel err 2.3e-7 (float32), 2.0e-3
    # (bfloat16) — chip run, PR 21
    "so2_conv": "pallas",
    # compiled on a TPU v5 lite at eSCN-MD's (E_c=32768, 9 * 128) rows with
    # the 35 block entries as float32 columns, both directions and the
    # columns' cotangent; agreement and timings: PERF.md section 6, PR 31
    "wigner_rotate": "pallas",
    # compiled on a TPU v5 lite at MACE's (E_c=32768, nQ=40, 128) chunk into
    # a flat (29568, 5120) carry and UMA's (32768, 1152) rows into (9856,
    # 1152); max rel err 4.1e-7 (float32), 2.8e-3 (bfloat16) — chip run,
    # PR 33; timings: PERF.md section 6
    "segment_sum_into": "pallas",
    # the transpose of DimeNet++'s repeat over a slab: compiled on a TPU v5
    # lite at (394368, 128) float32 rows into 8448 sorted centres, 1.82 ms
    # a call in edge blocks of 1664 against 4.16 for XLA's sorted scatter
    # (2.04 / 4.06 into two rows a centre); max rel err 6.5e-6 (float32)
    # — chip run on a TPU v5e; PERF.md section 6
    "segment_repeat": "pallas",
}


@dataclass
class KernelCounter:
    """Trace-time tally of dispatch decisions, in total and per fused op
    (``ops[op] = [pallas, xla]`` call sites)."""

    pallas: int = 0
    xla: int = 0
    ops: dict = field(default_factory=dict)

    @property
    def total(self) -> int:
        return self.pallas + self.xla

    @property
    def coverage(self) -> float:
        return self.pallas / self.total if self.total else 0.0

    @property
    def mode(self) -> str:
        if self.total == 0:
            return ""
        return "pallas" if self.pallas > 0 else "xla"


@dataclass
class Gather:
    """A deferred node-row gather input to :func:`fused_edge_aggregate`.

    ``node`` is an (N, ...) array, ``idx`` the (E,) per-edge row indices.
    On the Pallas path small node arrays ride VMEM whole and the gather
    happens INSIDE the kernel; oversized ones (and the XLA fallback)
    pre-gather with a plain XLA gather.
    """

    node: Any
    idx: Any
    # populated by dispatch: node flattened trailing shape restored in rows
    trailing: tuple = field(default_factory=tuple)


@dataclass
class Repeat:
    """A bond-row input to :func:`fused_edge_aggregate` in its table form:
    the rows of ``node`` ``(num_segments, ...)`` at every slot of a
    slot-major table, i.e. ``node[segment_ids]`` without the index array."""

    node: Any


def force_kernel_mode(mode: str | None):
    """Context manager pinning the dispatch decision for the current
    thread: ``"pallas" | "interpret" | "xla" | None`` (None restores the
    env/backend default). Used by the contract checker's ``--kernels``
    flag and the parity tests."""

    @contextmanager
    def ctx():
        if mode is not None and mode not in _MODES:
            raise ValueError(f"mode={mode!r}: expected one of {_MODES}")
        old = getattr(_local, "forced", None)
        _local.forced = mode
        try:
            yield
        finally:
            _local.forced = old

    return ctx()


@contextmanager
def counting():
    """Collect this thread's dispatch decisions into a fresh counter
    (nested uses shadow the outer counter)."""
    old = getattr(_local, "counter", None)
    c = KernelCounter()
    _local.counter = c
    try:
        yield c
    finally:
        _local.counter = old


def _count(op: str, used_pallas: bool) -> None:
    c = getattr(_local, "counter", None)
    if c is not None:
        if used_pallas:
            c.pallas += 1
        else:
            c.xla += 1
        c.ops.setdefault(op, [0, 0])[0 if used_pallas else 1] += 1


def resolve_kernel_mode(kernels=None, *, op: str) -> str:
    """Static (trace-time) routing decision for fused op ``op`` (a key of
    :data:`TPU_DEFAULT_MODE`).

    Priority: :func:`force_kernel_mode` context > per-object ``kernels``
    (``False`` -> xla, ``"interpret"``/``"pallas"``/``"xla"`` verbatim)
    > ``DISTMLIP_KERNELS`` env (``0``/``off`` kill switch, ``interpret``,
    ``1``/``on``) > backend default (``TPU_DEFAULT_MODE[op]`` on a TPU
    backend, xla on any other). ``kernels=None``/``True`` both mean
    "backend default" — True cannot force a compiled Pallas kernel onto a
    CPU host.
    """
    forced = getattr(_local, "forced", None)
    if forced is not None:
        return forced
    if kernels is False:
        return "xla"
    if isinstance(kernels, str):
        if kernels not in _MODES:
            raise ValueError(f"kernels={kernels!r}: expected bool, None or "
                             f"one of {_MODES}")
        return kernels
    env = os.environ.get("DISTMLIP_KERNELS", "auto").strip().lower()
    if env in ("0", "off", "false", "xla"):
        return "xla"
    if env == "interpret":
        return "interpret"
    if env in ("1", "on", "force", "pallas"):
        return "pallas"
    return TPU_DEFAULT_MODE[op] if jax.default_backend() == "tpu" else "xla"


def _mask_mul(rows, mask):
    if mask is None:
        return rows
    m = mask.astype(rows.dtype)
    return rows * m.reshape(m.shape + (1,) * (rows.ndim - m.ndim))


def _int_zero(x):
    """float0 cotangent for an integer/bool primal (custom_vjp contract)."""
    import numpy as np

    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# fused segment sum
# ---------------------------------------------------------------------------

def fused_segment_sum(data, segment_ids, num_segments: int, mask=None,
                      indices_are_sorted: bool = False, kernels=None):
    """Dispatching drop-in for ``masked_segment_sum``.

    Routes to the dst-tiled Pallas kernel when the layout contract holds
    (``indices_are_sorted=True`` — the dst-tile slicing depends on it)
    and the mode resolves to Pallas; identical masking/padding semantics
    on both paths, custom VJP on the kernel path.
    """
    mode = resolve_kernel_mode(kernels, op="segment_sum")
    # float (inexact) masks would need a real mask cotangent (the bwd
    # returns float0) — all repo masks are boolean; float masks take the
    # XLA path where plain AD handles them
    float_mask = (mask is not None
                  and jnp.issubdtype(jnp.result_type(mask), jnp.inexact))
    use = (mode != "xla" and indices_are_sorted and not float_mask
           and data.shape[0] > 0 and num_segments > 0)
    _count("segment_sum", use)
    if not use:
        with scope("edge_aggregate"):
            return masked_segment_sum(data, segment_ids, num_segments, mask,
                                      indices_are_sorted=indices_are_sorted)
    # every traced operand is an EXPLICIT custom_vjp arg (ids/mask may be
    # tracers of an enclosing scan/checkpoint body — closing over them
    # would leak out of that trace when the backward replays); integer
    # and bool primals get float0 cotangents. Under remat the replayed
    # forward of this call can be fully dead (the bwd needs only ids/mask
    # residuals); XLA DCEs the pure replay, no bytes ship:
    # contract: allow(dead_compute)
    with scope("edge_aggregate"):
        return _segment_sum_vjp(num_segments, mode == "interpret",
                                jnp.result_type(data))(data, segment_ids, mask)


def _segment_sum_vjp(num_segments: int, interpret: bool, dtype,
                     stage: str | None = "edge_aggregate",
                     edge_blk: int | None = None):
    # shape/dtype are trace-time statics: they ride the factory closure,
    # NOT the custom_vjp residuals (residuals must be valid JAX types —
    # they become scan carries when the call sits inside a scanned body)
    @jax.custom_vjp
    def f(d, ids, m):
        return pallas_segment_sum(d, ids, num_segments, mask=m,
                                  edge_blk=edge_blk, interpret=interpret)

    def fwd(d, ids, m):
        return f(d, ids, m), (ids, m)

    def bwd(res, g):
        return _segment_sum_bwd(*res, g, dtype, stage)

    f.defvjp(fwd, bwd)
    return f


def _segment_sum_bwd(ids, m, g, dtype, stage: str | None = "edge_aggregate"):
    """Cotangents ``(rows, ids, mask)`` of a masked segment sum: its
    transpose is the sorted per-edge gather; ids and mask get float0.
    ``stage`` is the scope the gather opens (None: the caller's)."""
    with _scoped(stage):
        gd = jnp.take(g, ids, axis=0)
        m_ct = None if m is None else _int_zero(m)
        return (_mask_mul(gd, m).astype(dtype), _int_zero(ids), m_ct)


def _scoped(stage: str | None):
    return scope(stage) if stage else nullcontext()


def lane_width(width: int, op: str, kernels=None) -> int:
    """The row width ``op``'s kernel takes for rows ``width`` wide: the
    next multiple of the 128 lanes where the kernel path engages (Mosaic
    refuses blocks off the lane grid), ``width`` itself on the XLA path. A
    caller that pads its rows once, before it gathers them, makes no padded
    copy a call."""
    if resolve_kernel_mode(kernels, op=op) == "xla":
        return width
    return -(-width // 128) * 128


def fused_segment_repeat(rows, segment_ids, kernels=None):
    """``rows[segment_ids]`` where the rows come in pairs and the members
    of a list read a row of their pair in pair order (``rows``
    ``(num_segments, W)``, ``segment_ids // 2`` nondecreasing: the kernel's
    dst tiles, whole multiples of 8 rows, cut no pair). The transpose is
    the segment sum of the members' cotangents onto their rows: on the
    Pallas path the dst-tiled :func:`pallas_segment_sum` through a
    ``custom_vjp`` (``W`` its :func:`lane_width`), elsewhere XLA's
    scatter-add; half-precision rows add up in float32 and round once
    either way. No scope of its own: the gather, the sum and, under a
    second derivative, the sum's own transpose read under the caller's
    stage."""
    mode = resolve_kernel_mode(kernels, op="segment_repeat")
    use = mode != "xla" and segment_ids.shape[0] > 0 and rows.shape[0] > 0
    _count("segment_repeat", use)
    half = str(rows.dtype) in ("bfloat16", "float16")
    if not use:
        f32 = rows.astype(jnp.float32) if half else rows
        return f32[segment_ids].astype(rows.dtype)
    # every traced operand explicit, statics in the closure: as in
    # fused_segment_sum
    return _segment_repeat_vjp(rows.shape[0], mode == "interpret",
                               rows.dtype)(rows, segment_ids)


def repeat_edge_block(n: int) -> int:
    """The edge block of :func:`fused_segment_repeat`'s kernel over ``n``
    member rows: the largest multiple of 128 that divides ``n``, up to
    2048, so that no padded copy of the rows is made and the kernel takes
    few, long steps; where that is under 384, 1024 rows (or ``n`` rounded
    up to 128 where fewer) and a padded copy. On a v5e, ``(n, 128)``
    float32 rows summed onto two rows of each of 8,448 centres: at 394,368
    rows 1.97 ms a call in blocks of 1,664, 2.12 in 384, 2.54 in 1,024
    padded, 2.96 in the kernel's default 256 padded, 3.05 in 128; at
    394,624 rows (128 times a prime) 2.60 in 1,024 padded, 3.01 in 256,
    3.07 in 128; XLA's sorted scatter-add 4.05-4.07 (PERF.md section 6)."""
    best = next((b for b in range(2048, 0, -128) if n % b == 0), 0)
    return best if best >= 384 else min(1024, -(-n // 128) * 128)


def _segment_repeat_vjp(num_segments: int, interpret: bool, dtype):
    @jax.custom_vjp
    def f(rows, ids):
        return jnp.take(rows, ids, axis=0)

    def fwd(rows, ids):
        return f(rows, ids), ids

    def bwd(ids, g):
        total = _segment_sum_vjp(num_segments, interpret, g.dtype, None,
                                 repeat_edge_block(ids.shape[0]))(g, ids, None)
        return total.astype(dtype), _int_zero(ids)

    f.defvjp(fwd, bwd)
    return f


def segment_sum_carry(num_segments: int, out_shape, dtype, kernels=None):
    """The zero carry of a scan of :func:`fused_segment_sum_into`, read
    back with :func:`segment_sum_result`.

    On the XLA path the ``(num_segments,) + out_shape`` array itself. On
    the Pallas path a pair: the accumulator as the kernel's flat
    ``(num_segments, W)`` rows (a ``(.., 40, 128)`` bfloat16 array pads 40
    sublanes to 48, so handing it to the kernel as rows would copy it
    every chunk), and a zero *shadow* in the result's own shape that the
    forward never reads. The shadow exists for its cotangent: the result's
    own (:func:`segment_sum_result`), which so reaches every chunk's
    backward in the shape the rows' gather reads, where the flat cotangent
    would be laid out anew chunk by chunk.
    """
    shape = (num_segments,) + tuple(out_shape)
    if resolve_kernel_mode(kernels, op="segment_sum_into") == "xla":
        return jnp.zeros(shape, dtype=dtype)
    return (jnp.zeros((num_segments, math.prod(shape[1:])), dtype=dtype),
            jnp.zeros(shape, dtype=dtype))


def segment_sum_result(carry):
    """The ``(num_segments,) + out_shape`` sum a carry holds."""
    if not isinstance(carry, tuple):
        return carry
    acc, shadow = carry
    return _shadowed(acc.reshape(shadow.shape), shadow)


@jax.custom_vjp
def _shadowed(x, shadow):
    """``x``; the shadow gets ``x``'s cotangent too."""
    return x


_shadowed.defvjp(lambda x, shadow: (x, None), lambda _, g: (g, g))


def fused_segment_sum_into(carry, data, segment_ids, mask=None, kernels=None):
    """The carry (:func:`segment_sum_carry`) with one dst-sorted chunk of
    an edge scan (``LocalGraph.scan_edges``) added: ``acc +
    masked_segment_sum(data, segment_ids, num_segments, mask)``, ``mask``
    boolean.

    On the Pallas path the sum is added into the flat accumulator in
    place, over the dst tiles between the chunk's first and last id only
    (:func:`pallas_segment_sum_into`): no ``(num_segments, W)`` kernel
    result, no whole-array add per chunk. The XLA path is the expression
    above as written.
    """
    if not isinstance(carry, tuple):
        _count("segment_sum_into", False)
        with scope("edge_aggregate"):
            return carry + masked_segment_sum(
                data, segment_ids, carry.shape[0], mask,
                indices_are_sorted=True)
    mode = resolve_kernel_mode(kernels, op="segment_sum_into")
    _count("segment_sum_into", True)
    # every traced operand explicit, statics in the closure: as in
    # fused_segment_sum. Under remat the replayed forward of this call is
    # dead (the bwd needs only ids/mask, the carry's cotangents pass
    # through), so the carry is no residual of the scan:
    # contract: allow(dead_compute)
    with scope("edge_aggregate"):
        return _segment_sum_into_vjp(mode == "interpret",
                                     jnp.result_type(data))(
            *carry, data, segment_ids, mask)


def _segment_sum_into_vjp(interpret: bool, dtype):
    @jax.custom_vjp
    def f(acc, shadow, d, ids, m):
        return pallas_segment_sum_into(acc, d, ids, mask=m,
                                       interpret=interpret), shadow

    def fwd(acc, shadow, d, ids, m):
        return f(acc, shadow, d, ids, m), (ids, m)

    def bwd(res, g):
        g_acc, g_shadow = g
        return (g_acc, g_shadow, *_segment_sum_bwd(*res, g_shadow, dtype))

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# fused gather -> edge compute -> scatter
# ---------------------------------------------------------------------------

def _jaxpr_call(jaxpr, n_rows: int):
    """``fun(*rows, *consts)`` re-evaluating a traced edge_fn jaxpr with
    its hoisted consts as explicit trailing arguments."""

    def fun(*args):
        rows, cs = args[:n_rows], args[n_rows:]
        out = jax.core.eval_jaxpr(jaxpr, list(cs), *rows)
        if len(out) != 1:
            raise ValueError("edge_fn must return a single array")
        return out[0]

    return fun


def _hoist(edge_fn, row_avals):
    """Trace ``edge_fn`` at the given row shapes and hoist its closure
    captures (weights, tables). Returns ``(jaxpr, raw_consts)`` — the
    RAW captured objects, so two traces of the same function can be
    matched by identity (the jaxpr's shapes are baked, and the kernel
    and the chunked backward evaluate at different row counts)."""
    closed = jax.make_jaxpr(edge_fn)(*row_avals)
    return closed.jaxpr, list(closed.consts)


def _match_consts(raw_fwd, raw_bwd):
    """Position of each backward-trace const in the forward trace's const
    list. Tracing one function at two leading-axis sizes walks the same
    code path, so the captured objects are the same — anything else means
    a shape-dependent branch inside edge_fn, where silently dropping a
    cotangent would corrupt training grads: fail loudly instead."""
    id2fwd = {id(c): i for i, c in enumerate(raw_fwd)}
    perm = [id2fwd.get(id(c)) for c in raw_bwd]
    if None in perm or len(set(perm)) != len(raw_fwd):
        raise ValueError(
            "fused_edge_aggregate: edge_fn's closure captures differ "
            "between the kernel-block and backward-chunk traces (shape-"
            "dependent capture set); pass kernels=False for this call "
            "site or restructure edge_fn")
    return perm


def _rows_of(item, slabs: int = 0):
    """Materialize one input's per-edge rows (XLA path / backward).

    Half-precision node arrays gather through an fp32 view: the gather's
    TRANSPOSE is a scatter-add of per-edge cotangents into the node rows,
    and routing it through fp32 accumulates those contributions at full
    precision with one rounding at the end (the dtype_discipline
    contract) — the forward rows are bit-identical (upcast/downcast of
    the same values) and the convert fuses into the gather."""
    if isinstance(item, Repeat):
        return slab_repeat(jnp.asarray(item.node), slabs)  # same rule
    if isinstance(item, Gather):
        node = jnp.asarray(item.node)
        if str(node.dtype) in ("bfloat16", "float16"):
            return jnp.take(node.astype(jnp.float32), item.idx,
                            axis=0).astype(node.dtype)
        return jnp.take(node, item.idx, axis=0)
    return jnp.asarray(item)


def fused_edge_aggregate(edge_fn, inputs, segment_ids, num_segments: int,
                         mask=None, indices_are_sorted: bool = True,
                         kernels=None, diff_params: bool = True,
                         vmem_budget: int | None = None,
                         bwd_chunk: int | None = None,
                         stages: tuple = ("edge_message", "edge_aggregate"),
                         slabs: int = 0):
    """Fused gather + per-edge compute + dst-sorted segment sum.

    ``inputs``: per-edge arrays ``(E, ...)`` and/or :class:`Gather`
    markers. ``edge_fn(*rows) -> (E,) + out_shape`` messages; the result
    is ``sum_{e: dst[e]=n} mask[e] * edge_fn(...)[e]`` with the exact
    ``masked_segment_sum`` padding semantics. On the Pallas path the
    message tensor only ever exists one ``(BLK, width)`` block at a time
    in VMEM — forward AND backward (chunked custom VJP).

    ``diff_params``: whether gradients flow into ``edge_fn``'s hoisted
    float closure captures (edge-MLP weights). Training programs need
    True (the default). Force/stress programs differentiate positions
    only — they pass False, which stop-gradients the captures so the
    custom VJP neither computes the (dead) weight cotangents nor emits
    the replicated-input psums shard_map's transpose would otherwise
    add for them (a custom_vjp marks every primal perturbed; without
    this knob the kernel path would ship weight-gradient bytes over the
    mesh on every force call that plain XLA AD never ships).

    ``stages``: the two scopes (telemetry/stages.py) this call opens,
    (message, aggregate). They are innermost, so a caller's own scope
    around the call loses to them: a call over another graph than the
    atom graph (CHGNet's lines) names its own here.

    Table form (``slabs > 0``, ``segment_ids`` None): the rows are a
    slot-major table of ``slabs * num_segments`` slots, row
    ``k * num_segments + n`` the k-th of segment ``n`` (CHGNet's in-line
    table, ``partition/graph.line_table``). ``inputs`` may then hold
    :class:`Repeat` markers, rows addressed by the segment itself. On the
    XLA path those are repeats and the sum is a sum over the slabs, no index
    traffic; the kernel tiles a dst-sorted list, which the table becomes by
    a static transposition (slot-major to dst-major).
    """
    inputs = list(inputs)
    msg_stage, agg_stage = stages
    mode = resolve_kernel_mode(kernels, op="edge_aggregate")
    e = slabs * num_segments if slabs else int(segment_ids.shape[0])
    # float (inexact) masks would need a mask cotangent the chunked
    # backward doesn't produce — every mask in this repo is boolean; a
    # float mask routes to the XLA path where plain AD handles it
    float_mask = (mask is not None
                  and jnp.issubdtype(jnp.result_type(mask), jnp.inexact))
    use = (mode != "xla" and indices_are_sorted and e > 0
           and num_segments > 0 and not float_mask)
    _count("edge_aggregate", use)
    if not use:
        # stages (telemetry/stages.py): the src-row gathers and the
        # per-edge compute are the message (edge_fn's own scopes, e.g. a
        # radial MLP inside it, are innermost and win), the sum is the
        # aggregate; the fused kernel below is one operation: aggregate
        with scope(msg_stage):
            msg = edge_fn(*[_rows_of(i, slabs) for i in inputs])
        with scope(agg_stage):
            if slabs:
                return slab_sum(msg, num_segments, mask)
            return masked_segment_sum(msg, segment_ids, num_segments, mask,
                                      indices_are_sorted=indices_are_sorted)

    if slabs:
        with scope(msg_stage):
            def dst_major(x):
                x = jnp.asarray(x)
                return x.reshape((slabs, num_segments) + x.shape[1:]
                                 ).swapaxes(0, 1).reshape(x.shape)

            segment_ids = jnp.repeat(
                jnp.arange(num_segments, dtype=jnp.int32), slabs)
            inputs = [Gather(i.node, segment_ids) if isinstance(i, Repeat)
                      else Gather(i.node, dst_major(i.idx))
                      if isinstance(i, Gather) else dst_major(i)
                      for i in inputs]
            mask = None if mask is None else dst_major(mask)

    interpret = mode == "interpret"
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else int(vmem_budget)
    chunk = DEFAULT_BWD_CHUNK if bwd_chunk is None else int(bwd_chunk)

    # oversized node arrays: pre-gather with XLA (the kernel's in-kernel
    # gather wants the node array VMEM-resident)
    prep = []
    for item in inputs:
        if isinstance(item, Gather):
            node = jnp.asarray(item.node)
            if node.size * node.dtype.itemsize > budget:
                prep.append(_rows_of(item))
            else:
                prep.append(Gather(node, item.idx, node.shape[1:]))
        else:
            prep.append(jnp.asarray(item))

    # per-edge row avals at an arbitrary leading size (the jaxpr shapes
    # are baked, so the kernel traces at its block size and the backward
    # at its chunk size)
    def avals_at(n):
        return [
            jax.ShapeDtypeStruct((n,) + tuple(p.trailing), p.node.dtype)
            if isinstance(p, Gather)
            else jax.ShapeDtypeStruct((n,) + p.shape[1:], p.dtype)
            for p in prep
        ]

    out_aval = jax.eval_shape(edge_fn, *avals_at(e))
    out_shape, out_dtype = out_aval.shape[1:], out_aval.dtype

    # hoist edge_fn's closure captures (edge-MLP weights, coupling tables)
    # into explicit arrays: a Pallas kernel cannot capture array constants,
    # and parameter captures must stay DIFFERENTIABLE (training grads flow
    # through the per-edge compute). conv_fn(*rows, *consts) is edge_fn
    # with its captures as trailing args; float consts become primal args
    # of the custom VJP, integer tables stay constant. (jax.closure_convert
    # hoists only TRACER captures — concrete weight arrays would stay baked
    # in and trip pallas_call's no-captured-constants check — so the
    # hoisting is done on an explicit jaxpr trace at the kernel's block
    # granularity.)
    from .segment import _pick_tiles

    tn, eb = _pick_tiles(e, num_segments, None, None)
    jaxpr_blk, raw_consts = _hoist(edge_fn, avals_at(eb))
    consts = [jnp.asarray(c) for c in raw_consts]
    if not diff_params:
        # force-only program: cut the capture gradients here, INSIDE the
        # shard-local function, so no weight-cotangent psum ever reaches
        # the shard_map boundary
        consts = [jax.lax.stop_gradient(c) for c in consts]
    conv_fn = _jaxpr_call(jaxpr_blk, len(prep))
    diff_cpos = [i for i, c in enumerate(consts)
                 if jnp.issubdtype(c.dtype, jnp.inexact)]
    n_in = len(prep)

    def merged_consts(dconsts):
        out = list(consts)
        for i, d in zip(diff_cpos, dconsts):
            out[i] = d
        return out

    # EVERY traced operand is an explicit custom_vjp primal — node/edge
    # arrays, gather index columns, segment ids, the mask and the hoisted
    # float consts. Closing over any of them would leak tracers out of an
    # enclosing scan/remat body when the backward replays under
    # higher-order AD (training differentiates THROUGH the force vjp).
    idxs = [p.idx for p in prep if isinstance(p, Gather)]
    n_idx = len(idxs)
    has_mask = mask is not None

    def split(args):
        arrs = args[:n_in]
        idxs_ = list(args[n_in:n_in + n_idx])
        ids_ = args[n_in + n_idx]
        m_ = args[n_in + n_idx + 1] if has_mask else None
        dconsts = args[n_in + n_idx + 1 + int(has_mask):]
        return arrs, idxs_, ids_, m_, dconsts

    @jax.custom_vjp
    def f(*args):
        arrs, idxs_, ids_, m_, dconsts = split(args)
        items = []
        gi = 0
        for p, a in zip(prep, arrs):
            if isinstance(p, Gather):
                items.append(("gather", a, idxs_[gi]))
                gi += 1
            else:
                items.append(a)
        return pallas_edge_aggregate(
            conv_fn, items, ids_, num_segments, m_,
            out_shape=out_shape, out_dtype=out_dtype,
            consts=merged_consts(dconsts), tile_n=tn, edge_blk=eb,
            interpret=interpret)

    def f_fwd(*args):
        return f(*args), args

    def f_bwd(args, g):
        arrs, idxs_, ids_, m_, dconsts = split(args)

        def make_rowwise(chunk_n):
            # re-trace at the backward's chunk granularity; the captures
            # are matched BY IDENTITY to the forward trace so the float
            # ones route through the custom-VJP args (grads flow)
            jaxpr_bwd, raw_bwd = _hoist(edge_fn, avals_at(chunk_n))
            perm = _match_consts(raw_consts, raw_bwd)
            bwd_fn = _jaxpr_call(jaxpr_bwd, n_in)

            def rowwise(rows, dconsts_):
                merged = merged_consts(list(dconsts_))
                return bwd_fn(*rows, *[merged[p] for p in perm])

            return rowwise

        with scope(agg_stage):
            in_cts, const_cts = _edge_aggregate_bwd(
                make_rowwise, prep, arrs, dconsts, idxs_,
                ids_, m_, g, chunk, diff_params)
        out = in_cts + tuple(_int_zero(i) for i in idxs_)
        out = out + (_int_zero(ids_),)
        if has_mask:
            out = out + (_int_zero(m_),)  # masks are bool/int (gated above)
        return out + const_cts

    f.defvjp(f_fwd, f_bwd)
    diff = ([p.node if isinstance(p, Gather) else p for p in prep]
            + idxs + [segment_ids] + ([mask] if has_mask else [])
            + [consts[i] for i in diff_cpos])
    # custom_vjp must return a cotangent for EVERY primal; when the
    # enclosing transpose needs only some, the rest (including their
    # scatter-adds) are dead and XLA DCEs them:
    # contract: allow(dead_compute)
    with scope(agg_stage):
        return f(*diff)


def _edge_aggregate_bwd(make_rowwise, prep, arrs, dconsts, idxs,
                        segment_ids, mask, g, chunk,
                        diff_params: bool = True):
    """Chunked backward: per edge chunk, re-run the per-edge compute under
    ``jax.vjp`` against the gathered message cotangent ``g[dst] * mask``
    and accumulate input cotangents — plain inputs stack per-chunk rows,
    gathered node arrays scatter-add (the audited unsorted grad-program
    scatter), hoisted float consts (edge-MLP weights) sum across chunks.
    Working set is O(chunk * width), not O(E * width). With
    ``diff_params=False`` the const cotangents are symbolic zeros (the
    caller stop-gradients the captures; computing real cotangents here
    would be pure dead work). Returns ``(input_cts, const_cts)``."""
    e = int(segment_ids.shape[0])
    chunk = max(1, min(chunk, e))
    k = -(-e // chunk)
    e_pad = k * chunk
    pad = e_pad - e

    def pad_rows(x, fill=0):
        if pad == 0:
            return x
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=fill)

    ids_p = jnp.concatenate(
        [segment_ids, jnp.broadcast_to(segment_ids[-1], (pad,))]
    ) if pad else segment_ids
    m = jnp.ones((e,), dtype=g.dtype) if mask is None else mask.astype(g.dtype)
    m_p = pad_rows(m)

    # per-edge xs streams: plain rows come from the primal arrays, gather
    # inputs stream their idx column (node arrays stay closed over)
    xs = [ids_p, m_p]
    gi = 0
    for p, a in zip(prep, arrs):
        if isinstance(p, Gather):
            xs.append(pad_rows(idxs[gi].astype(jnp.int32)))
            gi += 1
        else:
            xs.append(pad_rows(a))
    rowwise = make_rowwise(chunk)

    def chunk_fn(carry, xs_c):
        node_cts, const_cts = carry
        ids_c, m_c, *per_edge = xs_c
        rows = []
        for p, a, col in zip(prep, arrs, per_edge):
            if isinstance(p, Gather):
                # f32-view gather for half node arrays: under SECOND-order
                # AD (the force loss differentiates through this backward)
                # the take's transpose scatter-adds per-edge cotangents
                # into the node rows — same fp32-accumulation contract as
                # _rows_of; forward rows are bit-identical
                if str(a.dtype) in ("bfloat16", "float16"):
                    rows.append(jnp.take(a.astype(jnp.float32), col,
                                         axis=0).astype(a.dtype))
                else:
                    rows.append(jnp.take(a, col, axis=0))
            else:
                rows.append(col)
        # same f32-view rule for the message-cotangent gather: its
        # second-order transpose segment-sums per-edge rows back into the
        # (num_segments, width) cotangent — fp32 accumulation, one round
        if str(g.dtype) in ("bfloat16", "float16"):
            gm = jnp.take(g.astype(jnp.float32), ids_c,
                          axis=0).astype(g.dtype)
        else:
            gm = jnp.take(g, ids_c, axis=0)
        gm = gm * m_c.reshape(m_c.shape + (1,) * (gm.ndim - 1))
        if diff_params:
            msg, vjp_fn = jax.vjp(rowwise, tuple(rows), tuple(dconsts))
            row_cts, dc_cts = vjp_fn(gm.astype(msg.dtype))
        else:
            msg, vjp_fn = jax.vjp(
                lambda rs: rowwise(rs, tuple(dconsts)), tuple(rows))
            (row_cts,) = vjp_fn(gm.astype(msg.dtype))
            dc_cts = tuple(jnp.zeros(c.shape, c.dtype) for c in dconsts)
        new_node_cts = list(node_cts)
        plain_out = []
        gi = 0
        for p, col, ct in zip(prep, per_edge, row_cts):
            if isinstance(p, Gather):
                # contract: allow(scatter_hints) — grad-path transpose of
                # an unsorted gather (src order is not dst order). The
                # accumulator carries fp32 (node_cts0 below): half inputs
                # would otherwise round per edge AND per chunk.
                new_node_cts[gi] = new_node_cts[gi].at[col].add(
                    ct.astype(new_node_cts[gi].dtype))
                gi += 1
            else:
                plain_out.append(ct)
        new_const_cts = (tuple(c0 + c for c0, c in zip(const_cts, dc_cts))
                         if diff_params else const_cts)
        return (tuple(new_node_cts), new_const_cts), tuple(plain_out)

    # half-precision node arrays accumulate their cotangents in an fp32
    # carry (rounded back to the storage dtype once, after the scan) —
    # the dtype_discipline fp32-accumulation contract
    node_cts0 = tuple(
        jnp.zeros(a.shape, jnp.float32 if str(a.dtype) in
                  ("bfloat16", "float16") else a.dtype)
        for p, a in zip(prep, arrs) if isinstance(p, Gather))
    const_cts0 = tuple(jnp.zeros(c.shape, c.dtype) for c in dconsts)

    if k == 1:
        (node_cts, const_cts), plain = chunk_fn(
            (node_cts0, const_cts0), tuple(xs))
        plain = [c[:e] for c in plain]
    else:
        xs_c = tuple(x.reshape((k, chunk) + x.shape[1:]) for x in xs)
        (node_cts, const_cts), plain_stacked = jax.lax.scan(
            chunk_fn, (node_cts0, const_cts0), xs_c)
        plain = [c.reshape((e_pad,) + c.shape[2:])[:e]
                 for c in plain_stacked]

    out = []
    gi = pi = 0
    for p, a in zip(prep, arrs):
        if isinstance(p, Gather):
            out.append(node_cts[gi].astype(a.dtype))
            gi += 1
        else:
            out.append(plain[pi])
            pi += 1
    return tuple(out), tuple(const_cts)


# ---------------------------------------------------------------------------
# fused SO(2) convolution (eSCN channel mixing)
# ---------------------------------------------------------------------------

def fused_so2_conv(h, weights, m_idx: dict, channels: int, kernels=None,
                   diff_params: bool = True):
    """SO(2) convolution over all |m| blocks, dispatched.

    ``h``: (E, S, C) coefficients in the model's (e3nn) layout;
    ``weights``: ``[W0, W1r, W1i, ...]`` mixed (d, d) matrices per m;
    ``m_idx``: the model's per-|m| (plus, minus) index sets. Returns the
    convolved coefficients in the SAME layout. On the Pallas path every
    per-(l, m) GEMM runs in one VMEM-resident kernel; backward is the
    VJP of the XLA reference (the operand is already chunk-bounded by
    the model's edge scan). ``diff_params=False`` stop-gradients the
    weight stack (force/stress programs — same rationale as
    :func:`fused_edge_aggregate`); training keeps the default True.
    """
    perm, inv, segments = packed_m_layout(m_idx)

    def ref(h_, *ws):
        return so2_conv_reference(h_[:, perm, :], list(ws), segments,
                                  channels)[:, inv, :]

    mode = resolve_kernel_mode(kernels, op="so2_conv")
    use = mode != "xla" and h.shape[0] > 0
    _count("so2_conv", use)
    if not use:
        return ref(h, *weights)
    interpret = mode == "interpret"
    if not diff_params:
        weights = [jax.lax.stop_gradient(w) for w in weights]

    @jax.custom_vjp
    def f(h_, *ws):
        return so2_conv_pallas(h_[:, perm, :], list(ws), segments, channels,
                               interpret=interpret)[:, inv, :]

    def f_fwd(h_, *ws):
        return f(h_, *ws), (h_,) + ws

    def f_bwd(res, g):
        h_, ws = res[0], res[1:]
        if diff_params:
            _, vjp_fn = jax.vjp(ref, h_, *ws)
            return vjp_fn(g)
        _, vjp_fn = jax.vjp(lambda hh: ref(hh, *ws), h_)
        (gh,) = vjp_fn(g)
        return (gh,) + tuple(jnp.zeros(w.shape, w.dtype) for w in ws)

    f.defvjp(f_fwd, f_bwd)
    return f(h, *weights)


# ---------------------------------------------------------------------------
# Wigner rotation: lab rows <-> the edge frame's per-m pieces (eSCN-MD)
# ---------------------------------------------------------------------------

def fused_wigner_rotate(cols, ins, lay, *, to_edge: bool, kernels=None):
    """Rotation by per-edge Wigner blocks given as columns, dispatched.

    ``cols``: ``(E, n_cols)`` float32, the blocks' entries
    (``so3.wigner_cols``). ``to_edge``: ``ins`` is a tuple of lab operands
    ``(E, S * c)`` and the result the dict ``{m: (E, nl_m * len(ins) * c)}``
    of ``lay.signed_ms`` pieces, a degree's lanes running through ``ins`` in
    order. Otherwise ``ins`` is a dict of pieces (``c`` lanes a degree;
    absent pieces are skipped, not multiplied as zeros) and the result one
    lab operand ``(E, S * c)``. ``lay``: the model's ``CoeffLayout``.

    The kernel path is taken when the mode resolves to Pallas and ``c`` is
    a multiple of the 128 lanes; its backward is the same kernels (the
    rows' cotangent is the opposite rotation, the columns' a third pass).
    Otherwise the batched per-l products of ``so3.wigner_rotate_reference``.
    """
    if to_edge:
        ms, n_ops, arrays = tuple(lay.signed_ms), len(ins), tuple(ins)
        c = arrays[0].shape[1] // (lay.l_max + 1) ** 2
    else:
        ms, n_ops = tuple(m for m in lay.signed_ms if m in ins), 1
        arrays = tuple(ins[m] for m in ms)
        c = ins[0].shape[1] // lay.m_size(0)
    statics = dict(l_max=lay.l_max, m_max=lay.m_max, ms=ms, channels=c,
                   n_ops=n_ops)
    mode = resolve_kernel_mode(kernels, op="wigner_rotate")
    use = mode != "xla" and c % 128 == 0 and cols.shape[0] > 0
    _count("wigner_rotate", use)
    if use:
        rot_edge, rot_lab, _ = _wigner_vjps(mode == "interpret", **statics)
        out = (rot_edge if to_edge else rot_lab)(cols, *arrays)
    else:
        out = wigner_rotate_reference(cols, arrays, to_edge=to_edge,
                                      **statics)
    return dict(zip(ms, out)) if to_edge else out[0]


def _wigner_vjps(interpret: bool, *, n_ops: int, **statics):
    """``(to_edge, to_lab, dcols)`` over the Pallas kernels, each a
    ``custom_vjp`` whose backward is the other two, so any order of
    derivative stays on the kernels. Every traced operand is an explicit
    argument (the calls sit inside scanned, checkpointed bodies); the
    statics ride this closure."""
    rotate = partial(wigner_rotate_pallas, n_ops=n_ops, interpret=interpret,
                     **statics)

    @jax.custom_vjp
    def to_edge(cols, *labs):
        return rotate(cols, labs, to_edge=True)

    @jax.custom_vjp
    def to_lab(cols, *pieces):
        return rotate(cols, pieces, to_edge=False)

    @jax.custom_vjp
    def dcols(labs, pieces):
        return wigner_dcols_pallas(labs, pieces, interpret=interpret,
                                   **statics)

    def fwd(f):
        return lambda cols, *ins: (f(cols, *ins), (cols, ins))

    def to_edge_bwd(res, g):
        cols, labs = res
        return (dcols(labs, tuple(g)), *to_lab(cols, *g))

    def to_lab_bwd(res, g):
        cols, pieces = res
        return (dcols(tuple(g), pieces), *to_edge(cols, *g))

    def dcols_bwd(res, g):
        labs, pieces = res
        return (tuple(x.astype(a.dtype) for x, a in
                      zip(to_lab(g, *pieces), labs)),
                tuple(x.astype(a.dtype) for x, a in
                      zip(to_edge(g, *labs), pieces)))

    to_edge.defvjp(fwd(to_edge), to_edge_bwd)
    to_lab.defvjp(fwd(to_lab), to_lab_bwd)
    dcols.defvjp(lambda labs, pieces: (dcols(labs, pieces), (labs, pieces)),
                 dcols_bwd)
    return to_edge, to_lab, dcols
