"""Static HBM planner: buffer-liveness peak-memory analysis of traced programs.

Answers the question every OOM postmortem asks — *how many bytes is this
program's peak live set, and which buffers own it* — WITHOUT a chip and
without compiling: one pass over the :mod:`distmlip_tpu.analysis.ir`
walker's view of the jaxpr, before XLA ever sees the program. The result
drives three consumers:

- the ``memory_budget`` contract pass (``analysis/passes/memory_budget.py``)
  gates CI on a program's estimated peak vs the device ``bytes_limit``;
- memory-aware autobatching (``BucketPolicy.calibrate_bytes`` /
  ``serve.scheduler.plan_batch``) fills batches to an HBM budget instead of
  a fixed slot count;
- telemetry (``StepRecord.est_peak_bytes`` / ``hbm_headroom_frac``)
  compares the prediction against the backend's measured ``bytes_in_use``
  so estimator drift is visible on real hardware.

Estimator model
---------------
The walk is a sequential interpretation of the (nested) jaxpr:

- every aval is sized as ``prod(shape) * itemsize``;
- non-donated program inputs and baked consts are resident for the whole
  program (XLA holds caller-owned buffers); a DONATED input dies at its
  last use — its buffer is reusable from there on;
- a temporary lives from the eqn that defines it to its last use; eqn
  *transient* residency counts inputs AND outputs simultaneously (an op
  cannot free its operands before it finishes);
- call-like sub-jaxprs (pjit / remat / custom-vjp / shard_map bodies) are
  INLINED, exactly as XLA inlines them: a buffer crossing the boundary
  dies at its true last use inside the body, not at the call's end — the
  residuals feeding a grad program's transposed shard_map free
  progressively as the backward consumes them;
- ``scan``/``while``/``cond``/``pallas_call`` stay opaque: operands are
  held for the whole call (a loop needs them every iteration), the body's
  standalone peak is charged as call transient, and loops additionally
  charge a second copy of the carry (XLA double-buffers loop state it
  cannot prove aliasable); a scan's stacked ``ys`` are full-length
  outputs at the call site;
- ``shard_map`` bodies carry per-shard avals, so everything produced
  inside (including the residuals aliased out) is per-device sized
  automatically; program *arguments* consumed by a shard_map are scaled
  by the product of the mesh axis sizes their ``in_names`` entry shards
  over, making the reported peak a PER-DEVICE estimate;
- ``pallas_call`` scratch (body refs beyond the operands/outputs) is
  charged as transient VMEM/HBM residency of the call eqn.

Two XLA realities the pure jaxpr walk cannot see are modeled explicitly
(both calibrated against ``compile().memory_analysis()`` on the repo's 22
contract-check programs — the estimator-vs-oracle test pins the 2x band):

- **fusion** (forward bias: overestimate): ``broadcast_in_dim`` / ``iota``
  / shape-only views never materialize — XLA fuses them into consumers —
  so their outputs are charged zero bytes (``VIRTUAL_PRIMS``);
- **scheduler slack** (backward bias: underestimate): XLA's list scheduler
  is not memory-minimizing — in a region dominated by UNFUSABLE ops
  (gather/slice/pad/concatenate/scatter), independent chains' buffers
  coexist far beyond jaxpr-order liveness (measured: the eSCN SO(2)-conv
  backward holds ~24 such buffers at its scheduled peak where jaxpr order
  needs ~6). Each region is therefore charged at least
  ``SCHED_SLACK_FRAC`` x the summed output bytes of its unfusable eqns
  (``UNFUSABLE_PRIMS``) — the fraction of a region's materialized
  working set a greedy schedule realistically keeps live at once.

Nothing here imports the runtime: the module is importable (and the
analysis runnable) with zero devices, zero compiles.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from . import ir

# prims whose outputs XLA always fuses into consumers — never materialized
VIRTUAL_PRIMS = frozenset({
    "broadcast_in_dim", "iota", "reshape", "squeeze", "expand_dims",
    "rev", "bitcast_convert_type",
})

# ops XLA cannot fuse into elementwise clusters: their outputs genuinely
# materialize, and a region full of them schedules with poor buffer reuse
UNFUSABLE_PRIMS = frozenset({
    "gather", "slice", "dynamic_slice", "dynamic_update_slice", "pad",
    "concatenate", "sort", "copy",
}) | ir.SCATTER_PRIMS

# fraction of a region's unfusable working set charged as simultaneously
# live (scheduler slack; calibrated against XLA memory_analysis on the
# repo's contract-check programs — see tests/test_memory_plan.py)
SCHED_SLACK_FRAC = 0.7

# loop primitives whose carried state XLA double-buffers
LOOP_PRIMS = frozenset({"scan", "while"})


def aval_bytes(aval) -> int:
    """Byte size of one abstract value (0 for tokens/opaque avals)."""
    try:
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is None:
            return 0
        if not shape:
            return int(np.dtype(dtype).itemsize)
        return int(np.prod(shape)) * int(np.dtype(dtype).itemsize)
    except Exception:  # noqa: BLE001 - exotic aval: size unknown
        return 0


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


@dataclass
class Contributor:
    """One buffer in the live set at the program's estimated peak."""

    nbytes: int
    shape: tuple
    dtype: str
    kind: str                     # "argument" | "const" | "temp"
    primitive: str = ""           # producing primitive ("" for args/consts)
    location: tuple | None = None  # (file, line) best effort
    path: tuple = ()              # enclosing control-flow path

    def where(self) -> str:
        loc = (f"{self.location[0]}:{self.location[1]}"
               if self.location else "<unknown>")
        via = f" via {'/'.join(self.path)}" if self.path else ""
        return loc + via

    def render(self) -> str:
        src = self.primitive or self.kind
        return (f"{self.nbytes / 2**20:8.2f} MiB  {src:<18} "
                f"{list(self.shape)!s:<20} {self.dtype:<10} {self.where()}")


@dataclass
class TransientWindow:
    """An eqn whose own transient allocation is a large slice of the peak —
    the 2x-residency windows (both sides of a copy/scatter/loop live at
    once) an OOM bisect should look at first."""

    nbytes: int                   # transient bytes charged at this eqn
    primitive: str
    location: tuple | None = None
    path: tuple = ()

    def render(self) -> str:
        loc = (f"{self.location[0]}:{self.location[1]}"
               if self.location else "<unknown>")
        via = f" via {'/'.join(self.path)}" if self.path else ""
        return (f"{self.nbytes / 2**20:8.2f} MiB transient  "
                f"{self.primitive:<18} {loc}{via}")


@dataclass
class MemoryPlan:
    """Per-device peak-memory estimate for one traced program."""

    peak_bytes: int = 0           # estimated per-device peak live set
    arg_bytes: int = 0            # program inputs (per-device where sharded)
    const_bytes: int = 0          # baked consts
    out_bytes: int = 0            # program outputs
    temp_peak_bytes: int = 0      # peak_bytes - resident args/consts
    n_eqns: int = 0               # eqns walked (nested included)
    contributors: list = field(default_factory=list)   # top-k at the peak
    transients: list = field(default_factory=list)     # TransientWindows
    oracle_bytes: int | None = None  # XLA memory_analysis total, if computed

    @property
    def resident_bytes(self) -> int:
        return self.arg_bytes + self.const_bytes

    def headroom_frac(self, bytes_limit: int | None) -> float | None:
        """Remaining fraction of ``bytes_limit`` after the estimated peak
        (negative: the program does not fit). None when no limit known."""
        if not bytes_limit or bytes_limit <= 0:
            return None
        return 1.0 - self.peak_bytes / bytes_limit

    def render(self, top_k: int = 6) -> str:
        lines = [
            f"est peak {self.peak_bytes / 2**20:.2f} MiB per device "
            f"(args {self.arg_bytes / 2**20:.2f} + consts "
            f"{self.const_bytes / 2**20:.2f} + temps "
            f"{self.temp_peak_bytes / 2**20:.2f}; {self.n_eqns} eqns)"
        ]
        if self.oracle_bytes is not None:
            ratio = (self.peak_bytes / self.oracle_bytes
                     if self.oracle_bytes else float("inf"))
            lines.append(
                f"XLA oracle {self.oracle_bytes / 2**20:.2f} MiB "
                f"(estimate/oracle = {ratio:.2f}x)")
        for c in self.contributors[:top_k]:
            lines.append("  " + c.render())
        for t in self.transients[:top_k]:
            lines.append("  " + t.render())
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------


@dataclass
class _Buf:
    nbytes: int
    kind: str
    primitive: str = ""
    shape: tuple = ()
    dtype: str = ""
    location: tuple | None = None
    path: tuple = ()
    last_use: int = -1

    def contributor(self) -> Contributor:
        return Contributor(nbytes=self.nbytes, shape=self.shape,
                           dtype=str(self.dtype), kind=self.kind,
                           primitive=self.primitive, location=self.location,
                           path=self.path)


@dataclass
class _Step:
    """One flattened program step (inline-call boundaries dissolved)."""

    prim: str
    path: tuple
    region: int                   # owning region index (slack accounting)
    in_roots: list                # canonical buffer ids consumed
    out_roots: list               # canonical buffer ids produced
    out_bytes: int = 0
    extra: int = 0                # opaque body peak + carry/scratch bytes
    location: tuple | None = None
    inner_at_peak: list = field(default_factory=list)


class _Flat:
    """Flattened program: steps + buffer metadata + per-region sums."""

    def __init__(self):
        self.steps: list[_Step] = []
        self.bufs: dict[int, _Buf] = {}
        self.unfusable: dict[int, int] = {}   # region -> byte sum
        self.n_regions = 0
        self._next = 0
        self.n_eqns = 0
        self.transients: list[TransientWindow] = []
        self.const_roots: list[int] = []

    def new_root(self, buf: _Buf) -> int:
        self._next += 1
        self.bufs[self._next] = buf
        return self._next

    def new_region(self) -> int:
        self.n_regions += 1
        return self.n_regions - 1


def _shard_factor(spec, mesh) -> int:
    """How many ways one shard_map operand is split: product of the mesh
    axis sizes named by its ``in_specs`` PartitionSpec (one entry per
    dim: None, an axis name, or a tuple of names)."""
    factor = 1
    for axes in spec:
        if axes is None:
            continue
        for ax in (axes if isinstance(axes, tuple) else (axes,)):
            factor *= int(mesh.shape[ax])
    return factor


def _arg_shard_factors(jaxpr) -> dict:
    """``{id(invar): factor}`` for top-level program inputs that reach a
    ``shard_map`` eqn — the per-device residency divisor. Follows pjit
    bodies (invar -> body invar identity) so the factor survives jit
    wrapping. Unsharded / unseen args keep factor 1."""
    factors: dict[int, int] = {}

    def visit(jx, outer_ids):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            subs = ir.sub_jaxprs(eqn.params)
            if name == "shard_map":
                mesh = eqn.params["mesh"]
                for v, spec in zip(eqn.invars, eqn.params["in_specs"]):
                    if _is_literal(v):
                        continue
                    root = outer_ids.get(id(v))
                    if root is not None:
                        f = _shard_factor(spec, mesh)
                        factors[root] = max(factors.get(root, 1), f)
            elif subs and name in ("pjit", "closed_call", "core_call",
                                   "remat2", "custom_jvp_call",
                                   "custom_vjp_call",
                                   "custom_vjp_call_jaxpr"):
                for sub in subs:
                    sub = getattr(sub, "jaxpr", sub)
                    mapped = {}
                    for outer_v, inner_v in zip(eqn.invars, sub.invars):
                        if _is_literal(outer_v):
                            continue
                        root = outer_ids.get(id(outer_v))
                        if root is not None:
                            mapped[id(inner_v)] = root
                    if mapped:
                        visit(sub, mapped)

    top = {id(v): id(v) for v in jaxpr.invars}
    visit(jaxpr, top)
    return factors


def _pallas_scratch_bytes(eqn) -> int:
    """Scratch refs of a pallas_call body: body invars beyond the mapped
    operands and outputs ((in_refs, out_refs, scratch_refs) convention)."""
    subs = ir.sub_jaxprs(eqn.params)
    if not subs:
        return 0
    body = getattr(subs[0], "jaxpr", subs[0])
    n_mapped = len(eqn.invars) + len(eqn.outvars)
    extra = list(body.invars)[n_mapped:]
    return sum(aval_bytes(v.aval) for v in extra)


# call-like primitives XLA inlines: buffers flow through the boundary and
# die at their true last use inside, not at the call's end
INLINE_PRIMS = frozenset({
    "pjit", "closed_call", "core_call", "remat2", "remat",
    "custom_jvp_call", "custom_jvp_call_jaxpr",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "shard_map",
})


def _collect(jaxpr, env: dict, path: tuple, region: int, fl: _Flat) -> None:
    """Flatten one (sub)jaxpr into ``fl.steps``, dissolving inline-call
    boundaries. ``env`` maps this jaxpr's var ids to canonical buffer
    roots; inlined bodies get fresh envs (the same body object may be
    inlined at several call sites)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    # constvars are baked buffers (ClosedJaxpr consts): resident throughout
    for var in jaxpr.constvars:
        if id(var) in env:
            continue
        buf = _Buf(nbytes=aval_bytes(var.aval), kind="const",
                   shape=tuple(getattr(var.aval, "shape", ())),
                   dtype=getattr(var.aval, "dtype", ""), path=path)
        root = fl.new_root(buf)
        env[id(var)] = root
        fl.const_roots.append(root)

    for eqn in jaxpr.eqns:
        fl.n_eqns += 1
        prim = eqn.primitive.name
        subs = ir.sub_jaxprs(eqn.params, unwrap=False)

        if prim in INLINE_PRIMS and len(subs) == 1:
            body = getattr(subs[0], "jaxpr", subs[0])
            if len(body.invars) == len(eqn.invars):
                inner_env = {}
                for ov, iv in zip(eqn.invars, body.invars):
                    if _is_literal(ov):
                        inner_env[id(iv)] = fl.new_root(_Buf(
                            nbytes=0, kind="temp", primitive="literal"))
                    else:
                        root = env.get(id(ov))
                        if root is None:
                            root = fl.new_root(_Buf(
                                nbytes=aval_bytes(ov.aval), kind="temp"))
                            env[id(ov)] = root
                        inner_env[id(iv)] = root
                _collect(subs[0], inner_env, path + (prim,),
                         fl.new_region(), fl)
                # alias outer outvars to the body's producing buffers —
                # for shard_map the body avals are PER-SHARD, so sharded
                # outputs are per-device sized automatically
                for ov, bv in zip(eqn.outvars, body.outvars):
                    if _is_literal(bv):
                        env[id(ov)] = fl.new_root(_Buf(
                            nbytes=0, kind="temp", primitive="literal"))
                    else:
                        root = inner_env.get(id(bv))
                        if root is None:
                            root = fl.new_root(_Buf(
                                nbytes=aval_bytes(bv.aval), kind="temp"))
                            inner_env[id(bv)] = root
                        env[id(ov)] = root
                continue

        # opaque eqn: loops / cond / pallas_call / plain primitives.
        # Bodies are analyzed standalone (operands held by THIS step's
        # in_roots for the call duration — correct for loops, which need
        # their operands every iteration).
        extra = 0
        inner_at_peak: list = []
        for s in subs:
            r = _sub_peak(s, path + (prim,), fl)
            if r[0] > extra:
                extra, inner_at_peak = r
        if prim in LOOP_PRIMS:
            # double-buffered carry: XLA keeps the incoming and outgoing
            # loop state simultaneously when it cannot prove aliasing
            num_carry = eqn.params.get("num_carry")
            if num_carry is None:       # while: whole tuple is the carry
                carry_avals = [v.aval for v in eqn.outvars]
            else:
                carry_avals = [v.aval for v in eqn.outvars[:num_carry]]
            extra += sum(aval_bytes(a) for a in carry_avals)
        elif prim == "pallas_call":
            extra += _pallas_scratch_bytes(eqn)

        virtual = prim in VIRTUAL_PRIMS and not subs
        loc = ir.source_location(eqn)
        in_roots = [env[id(v)] for v in eqn.invars
                    if not _is_literal(v) and id(v) in env]
        out_roots = []
        out_b = 0
        for v in eqn.outvars:
            nb = 0 if virtual else aval_bytes(v.aval)
            buf = _Buf(nbytes=nb, kind="temp", primitive=prim,
                       shape=tuple(getattr(v.aval, "shape", ())),
                       dtype=getattr(v.aval, "dtype", ""),
                       location=loc, path=path)
            root = fl.new_root(buf)
            env[id(v)] = root
            out_roots.append(root)
            out_b += nb
        if prim in UNFUSABLE_PRIMS and not subs:
            fl.unfusable[region] = fl.unfusable.get(region, 0) + out_b
        fl.steps.append(_Step(
            prim=prim, path=path, region=region, in_roots=in_roots,
            out_roots=out_roots, out_bytes=out_b, extra=extra,
            location=loc, inner_at_peak=inner_at_peak))


def _sub_peak(sub, path, fl: _Flat):
    """Standalone peak of an opaque body (loop/cond/pallas): its invars
    are charged by the caller, so they enter at zero bytes here."""
    body = getattr(sub, "jaxpr", sub)
    sub_fl = _Flat()
    env = {id(v): sub_fl.new_root(_Buf(nbytes=0, kind="temp"))
           for v in body.invars}
    _collect(sub, env, path, sub_fl.new_region(), sub_fl)
    out_roots = [env[id(v)] for v in body.outvars
                 if not _is_literal(v) and id(v) in env]
    peak, at_peak = _simulate(sub_fl, 0, set(), out_roots)
    fl.n_eqns += sub_fl.n_eqns
    fl.transients.extend(sub_fl.transients)
    return peak, at_peak


def _simulate(fl: _Flat, resident_base: int, donated_roots: set,
              final_roots: list):
    """Liveness simulation over the flattened step list. Returns
    ``(peak_bytes, live buffers at the peak)`` and appends large transient
    windows to ``fl.transients``."""
    n = len(fl.steps)
    last: dict[int, int] = {}
    for i, step in enumerate(fl.steps):
        for r in step.in_roots:
            last[r] = i
    for r in final_roots:
        last[r] = n
    for r in fl.const_roots:
        last[r] = n                # baked consts stay resident

    live: dict[int, int] = {}      # root -> bytes (temps + donated args)
    cur = resident_base
    peak = resident_base
    at_peak: list[_Buf] = []
    region_entry: dict[int, int] = {}        # region -> cur at entry
    region_entry_step: dict[int, int] = {}   # region -> first step index

    for i, step in enumerate(fl.steps):
        if step.region not in region_entry:
            region_entry[step.region] = cur
            region_entry_step[step.region] = i
        transient = cur + step.out_bytes + step.extra
        if transient > peak:
            peak = transient
            at_peak = ([fl.bufs[r] for r in live]
                       + [fl.bufs[r] for r in step.out_roots]
                       + list(step.inner_at_peak))
        if step.extra > 0:
            fl.transients.append(TransientWindow(
                nbytes=step.out_bytes + step.extra, primitive=step.prim,
                location=step.location, path=step.path))
        cur += step.out_bytes
        for r in step.out_roots:
            nb = fl.bufs[r].nbytes
            if last.get(r, -1) <= i:        # unused output: freed at once
                cur -= nb
            elif nb:
                live[r] = nb
        for r in step.in_roots:
            if last.get(r) == i:
                if r in live:
                    cur -= live.pop(r)
                elif r in donated_roots:
                    cur -= fl.bufs[r].nbytes
                    donated_roots.discard(r)
        cur = max(cur, 0)

    # list-scheduler slack: whatever the flattened order says, a region
    # holds a calibrated fraction of its unfusable working set at once on
    # top of whatever was live when it started
    slack_region = None
    for region, unf in fl.unfusable.items():
        entry = region_entry.get(region, resident_base)
        slack = entry + int(SCHED_SLACK_FRAC * unf)
        if slack > peak:
            peak = slack
            slack_region = region
    if slack_region is not None:
        # the slack term set the final peak: the liveness-walk snapshot
        # describes a DIFFERENT (lower) maximum, so re-derive the live
        # set at the winning region's entry and attribute the slack
        # itself — contributor sites (and the memory_budget ERROR anchor
        # / suppression line) must point at the bytes that actually own
        # the peak
        entry_i = region_entry_step[slack_region]
        live2: dict[int, int] = {}
        for j, step in enumerate(fl.steps[:entry_i]):
            for r in step.out_roots:
                nb = fl.bufs[r].nbytes
                if nb and last.get(r, -1) > j:
                    live2[r] = nb
            for r in step.in_roots:
                if last.get(r) == j:
                    live2.pop(r, None)
        first = fl.steps[entry_i]
        slack_buf = _Buf(
            nbytes=peak - region_entry[slack_region], kind="temp",
            primitive="sched-slack",
            location=first.location, path=first.path)
        at_peak = [fl.bufs[r] for r in live2] + [slack_buf]
    return peak, at_peak


def analyze_memory(closed_jaxpr, donated=(), top_k: int = 8) -> MemoryPlan:
    """Estimate the per-device peak live bytes of one traced program.

    Parameters
    ----------
    closed_jaxpr : ClosedJaxpr (``jax.make_jaxpr`` output) or Jaxpr.
    donated : iterable of invar indices (or a bool mask) marking donated
        program inputs — their buffers die at last use instead of staying
        resident (``jax.jit(..., donate_argnums=...)`` semantics; tracing
        does not record donation, so the caller states it).
    top_k : how many live-set contributors / transient windows to keep.
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    invars = list(jaxpr.invars)
    donated = list(donated) if donated is not None else []
    if donated and isinstance(donated[0], (bool, np.bool_)):
        donated_idx = {i for i, d in enumerate(donated) if d}
    else:
        donated_idx = {int(i) for i in donated}

    shard = _arg_shard_factors(jaxpr)
    fl = _Flat()
    env: dict[int, int] = {}
    arg_bytes = 0
    donated_bytes = 0
    donated_roots: set[int] = set()
    for i, v in enumerate(invars):
        nb = aval_bytes(v.aval) // shard.get(id(v), 1)
        root = fl.new_root(_Buf(
            nbytes=nb, kind="argument",
            shape=tuple(getattr(v.aval, "shape", ())),
            dtype=getattr(v.aval, "dtype", "")))
        env[id(v)] = root
        if i in donated_idx:
            donated_bytes += nb
            donated_roots.add(root)
        else:
            arg_bytes += nb

    _collect(closed_jaxpr, env, (), fl.new_region(), fl)
    const_bytes = sum(fl.bufs[r].nbytes for r in fl.const_roots)
    out_bytes = sum(aval_bytes(v.aval) for v in jaxpr.outvars
                    if not _is_literal(v))
    final_roots = [env[id(v)] for v in jaxpr.outvars
                   if not _is_literal(v) and id(v) in env]

    # donated inputs start resident and die at their last use in the walk
    resident = arg_bytes + const_bytes + donated_bytes
    peak, peak_bufs = _simulate(fl, resident, donated_roots, final_roots)

    contributors = [b.contributor() for b in peak_bufs if b.nbytes > 0]
    if arg_bytes:
        contributors.append(Contributor(
            nbytes=arg_bytes, shape=(len(invars),), dtype="",
            kind="argument", primitive="", location=None, path=()))
    if const_bytes:
        contributors.append(Contributor(
            nbytes=const_bytes, shape=(len(fl.const_roots),), dtype="",
            kind="const", primitive="", location=None, path=()))
    contributors.sort(key=lambda c: -c.nbytes)

    transients = sorted(fl.transients, key=lambda t: -t.nbytes)
    # keep only windows that matter: >= 10% of the peak
    floor = max(peak // 10, 1)
    transients = [t for t in transients if t.nbytes >= floor][:top_k]

    return MemoryPlan(
        peak_bytes=int(peak),
        arg_bytes=int(arg_bytes),
        const_bytes=int(const_bytes),
        out_bytes=int(out_bytes),
        temp_peak_bytes=int(max(peak - resident, 0)),
        n_eqns=fl.n_eqns,
        contributors=contributors[:top_k],
        transients=transients,
    )


# ---------------------------------------------------------------------------
# XLA oracle (optional: needs a compile, still chip-free on CPU)
# ---------------------------------------------------------------------------


def _traced_with_x64(closed_jaxpr) -> bool:
    """Whether the program was traced under enable_x64: any 64-bit
    float/int aval (a no-x64 trace cannot contain one; an x64 trace
    carries at least its weak python-scalar literals as f64). The oracle
    replay must match the TRACE's x64 regime — a weak literal lowers to
    the wrong width otherwise."""
    def wide(aval):
        dt = getattr(aval, "dtype", None)
        return (dt is not None and np.dtype(dt).kind in "fiu"
                and np.dtype(dt).itemsize == 8)

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if wide(v.aval):
            return True
    for eqn in ir.iter_eqns(closed_jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            if wide(getattr(v, "aval", None)):
                return True
    return False


def oracle_peak_bytes(closed_jaxpr) -> int | None:
    """Compile the traced program and return XLA's own peak-memory total
    (argument + output + temp + alias bytes from
    ``lower().compile().memory_analysis()``), or None where the backend
    does not report it. This is the estimator's calibration oracle — a
    REAL compile, so orders of magnitude slower than :func:`analyze_memory`
    (tests and ``tools/memory_audit.py --oracle`` only)."""
    try:
        import jax
        from jax.extend.core import jaxpr_as_fun

        fn = jaxpr_as_fun(closed_jaxpr)
        shapes = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                  for v in closed_jaxpr.jaxpr.invars]
        # replay in the same x64 regime the program was traced under, so
        # every literal and weak scalar lowers at its traced width
        with jax.enable_x64(_traced_with_x64(closed_jaxpr)):
            ma = jax.jit(fn).lower(*shapes).compile().memory_analysis()
        total = (int(ma.argument_size_in_bytes)
                 + int(ma.output_size_in_bytes)
                 + int(ma.temp_size_in_bytes)
                 + int(ma.alias_size_in_bytes))
        return total if total > 0 else None
    except Exception:  # noqa: BLE001 - oracle is best-effort by contract
        return None


__all__ = [
    "MemoryPlan", "Contributor", "TransientWindow", "analyze_memory",
    "oracle_peak_bytes", "aval_bytes",
]
