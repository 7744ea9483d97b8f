"""Roofline accounting: arithmetic intensity + achieved vs peak FLOP/s.

Joins the two analytic planes the repo already maintains —
:func:`distmlip_tpu.utils.flops.model_flop_estimate` (FLOPs per step)
and :func:`distmlip_tpu.analysis.memory.analyze_memory` (bytes) — into
per-program :class:`RooflineRow` entries:

- **intensity** = flops / bytes_touched (FLOP per HBM byte). Bytes
  touched is the MINIMUM traffic ``arg + const + out`` of the traced
  program (every input is read at least once, every output written
  once); intermediate spills push the true number higher, so the
  intensity here is an UPPER bound and sits on the optimistic side of
  the ridge.
- **achieved** = flops / (time_s * n_devices) when a measured step time
  exists (bench JSONL, telemetry records); 0.0 otherwise.
- **mfu** = achieved / peak and **ridge** = peak FLOP/s / peak bytes/s,
  both peaks from :func:`~distmlip_tpu.utils.flops.device_peaks` (no
  entry on CPU runs — rows still render, utilization and bound read
  n/a).

Consumed by ``tools/roofline.py`` (CLI over the 28 contract-check
programs) and ``telemetry_report`` (roofline section when records carry
the needed fields). Host-side only; no jax imports at module scope.
"""

from __future__ import annotations

from dataclasses import dataclass


# primitives that do arithmetic (~1 FLOP per output element). Data
# movement (reshape/slice/gather/broadcast/convert/...) counts zero;
# dot_general is handled exactly below.
_FLOP_PRIMS = frozenset({
    "add", "add_any", "sub", "mul", "div", "rem", "neg", "abs", "sign",
    "max", "min", "pow", "integer_pow", "exp", "expm1", "log", "log1p",
    "sqrt", "rsqrt", "cbrt", "sin", "cos", "tan", "asin", "acos", "atan",
    "atan2", "sinh", "cosh", "tanh", "erf", "erfc", "logistic", "square",
    "reciprocal", "floor", "ceil", "round", "clamp", "nextafter",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "cumsum",
    "psum", "select_n", "eq", "ne", "lt", "le", "gt", "ge", "and", "or",
    "not", "xor", "is_finite",
})


def _shape_elems(shape) -> float:
    n = 1.0
    for d in shape:
        n *= max(int(d), 1)
    return n


def jaxpr_flop_estimate(closed_jaxpr) -> float:
    """FLOPs of one execution of the traced program, from the jaxpr.

    Exact for ``dot_general`` (2*M*N*K over the batched output), ~1 FLOP
    per output element for elementwise/reduce arithmetic, 2 per scatter
    update (read-modify-write), zero for pure data movement. Loop/branch
    bodies count ONCE per trace (same caveat as ``iter_sites``) — a
    ``device_md`` chunk's per-chunk cost is this times its trip count.

    This is the PADDED cost — what the device executes, masked lanes
    included — which is the right numerator for roofline/MFU accounting
    (the analytic :func:`utils.flops.model_flop_estimate` prices live
    atoms/edges instead; the gap between the two is padding waste).
    """
    from ..analysis.ir import iter_sites

    flops = 0.0
    for site in iter_sites(closed_jaxpr):
        eqn = site.eqn
        name = eqn.primitive.name
        try:
            out = sum(_shape_elems(v.aval.shape) for v in eqn.outvars)
        except Exception:  # noqa: BLE001 - abstract tokens
            out = 1.0
        if name == "dot_general":
            try:
                ((lc, _), _) = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval.shape
                k = 1.0
                for ax in lc:
                    k *= max(int(lhs[ax]), 1)
                flops += 2.0 * out * k
            except Exception:  # noqa: BLE001 - fall back
                flops += 2.0 * out
        elif name.startswith("conv"):
            flops += 2.0 * out
        elif "scatter" in name:
            try:
                upd = _shape_elems(eqn.invars[-1].aval.shape)
            except Exception:  # noqa: BLE001
                upd = out
            flops += 2.0 * upd
        elif name in _FLOP_PRIMS:
            flops += out
    return flops


def bytes_touched(plan) -> int:
    """Minimum HBM traffic of one step from a :class:`MemoryPlan`."""
    return int(getattr(plan, "arg_bytes", 0)
               + getattr(plan, "const_bytes", 0)
               + getattr(plan, "out_bytes", 0))


@dataclass
class RooflineRow:
    """One program's position on the roofline."""

    program: str
    flops: float = 0.0            # analytic FLOPs per step
    bytes: float = 0.0            # minimum HBM bytes per step
    time_s: float = 0.0           # measured step device time (0 = none)
    peak_flops: float = 0.0       # per-device peak FLOP/s (0 = unknown)
    peak_bytes_per_s: float = 0.0  # per-device HBM bandwidth (0 = unknown)
    n_devices: int = 1
    source: str = "cost_model"    # "measured" when time_s came from a run

    @property
    def intensity(self) -> float:
        return self.flops / self.bytes if self.bytes > 0 else 0.0

    @property
    def achieved_flops(self) -> float:
        """Aggregate achieved FLOP/s across the devices that ran it."""
        return self.flops / self.time_s if self.time_s > 0 else 0.0

    @property
    def mfu(self) -> float:
        total_peak = self.peak_flops * max(self.n_devices, 1)
        if total_peak <= 0 or self.time_s <= 0:
            return 0.0
        return self.achieved_flops / total_peak

    @property
    def ridge_bound(self) -> str:
        """Which roof limits this program: "compute" when its intensity
        clears the ridge ``peak_flops / peak_bytes_per_s`` (about 240
        FLOP/byte on a v5e), "memory" below it; "" when either peak is
        unknown (no basis to place the ridge)."""
        if (self.peak_flops <= 0 or self.peak_bytes_per_s <= 0
                or self.intensity <= 0):
            return ""
        ridge = self.peak_flops / self.peak_bytes_per_s
        return "compute" if self.intensity >= ridge else "memory"

    def as_dict(self) -> dict:
        return {
            "program": self.program,
            "flops": self.flops,
            "bytes": self.bytes,
            "intensity": round(self.intensity, 3),
            "time_s": self.time_s,
            "achieved_flops": self.achieved_flops,
            "peak_flops": self.peak_flops,
            "peak_bytes_per_s": self.peak_bytes_per_s,
            "n_devices": self.n_devices,
            "mfu": round(self.mfu, 6),
            "ridge_bound": self.ridge_bound,
            "source": self.source,
        }


def _fmt_si(x: float) -> str:
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(x) >= div:
            return f"{x / div:.2f}{suf}"
    return f"{x:.1f}"


def format_roofline_table(rows, title: str = "roofline") -> str:
    """Fixed-width table over :class:`RooflineRow` entries."""
    lines = [title,
             f"  {'program':<38} {'flops':>9} {'bytes':>9} {'F/B':>8} "
             f"{'time_s':>9} {'achieved':>9} {'mfu':>7} {'bound':>7}"]
    for r in rows:
        mfu = f"{r.mfu:.1%}" if r.mfu > 0 else "n/a"
        ach = _fmt_si(r.achieved_flops) if r.time_s > 0 else "n/a"
        t = f"{r.time_s:.5f}" if r.time_s > 0 else "n/a"
        lines.append(
            f"  {r.program:<38.38} {_fmt_si(r.flops):>9} "
            f"{_fmt_si(r.bytes):>9} {r.intensity:>8.2f} {t:>9} "
            f"{ach:>9} {mfu:>7} {r.ridge_bound or 'n/a':>7}")
    return "\n".join(lines)


def rows_from_records(records) -> list:
    """Roofline rows recoverable from telemetry StepRecords.

    Groups records by ``(kind, bucket_key)``; a group yields a row only
    when some record carries a FLOP estimate (``extra["flops_per_step"]``
    — bench/CLI-stamped; plain serving records don't have one). Bytes
    come from ``est_peak_bytes`` as a traffic PROXY (it is a live-set
    peak, not traffic — rows from records are for trending only, the
    jaxpr-accurate numbers come from ``tools/roofline.py``). Mixed
    rounds where only some records carry the fields degrade to fewer
    rows, never to a KeyError.
    """
    from ..utils.flops import device_peaks

    peak_flops, peak_bw = device_peaks() or (0.0, 0.0)
    groups: dict[tuple, list] = {}
    for r in records:
        key = (getattr(r, "kind", ""), getattr(r, "bucket_key", ""))
        groups.setdefault(key, []).append(r)
    rows = []
    for (kind, bucket), recs in sorted(groups.items()):
        flops = 0.0
        nbytes = 0.0
        times = []
        n_dev = 1
        for r in recs:
            extra = getattr(r, "extra", None) or {}
            try:
                f = float(extra.get("flops_per_step", 0.0) or 0.0)
            except (TypeError, ValueError):
                f = 0.0
            flops = max(flops, f)
            nbytes = max(nbytes, float(getattr(r, "est_peak_bytes", 0) or 0))
            t = (getattr(r, "timings", None) or {}).get("device_s", 0.0)
            if t and not getattr(r, "compiled", False):
                times.append(float(t))  # warm steps only — compiles skew
            n_dev = max(n_dev, int(getattr(r, "num_partitions", 0) or 0) or 1)
        if flops <= 0:
            continue
        times.sort()
        t_med = times[len(times) // 2] if times else 0.0
        name = kind + (f"[{bucket}]" if bucket else "")
        rows.append(RooflineRow(
            program=name, flops=flops, bytes=nbytes, time_s=t_med,
            peak_flops=peak_flops, peak_bytes_per_s=peak_bw, n_devices=n_dev,
            source="measured" if t_med > 0 else "cost_model"))
    return rows


__all__ = [
    "RooflineRow",
    "bytes_touched",
    "format_roofline_table",
    "jaxpr_flop_estimate",
    "rows_from_records",
]
