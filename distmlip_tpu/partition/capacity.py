"""Capacity bucketing: bound XLA recompiles under changing graph sizes.

Edge/halo counts change every MD step; XLA programs need static shapes. We
round every capacity up to a bucket so a recompile only happens when a count
outgrows its bucket (the reference never faced this — eager PyTorch —
see SURVEY.md §7 "Hard parts").

Two policies coexist:

- ``CapacityPolicy`` (sticky): caps only grow, per process. Right for a
  long MD/relax run of ONE system, where sizes drift slowly and the cap
  converges after a few steps.
- ``BucketPolicy`` (geometric, stateless): every request maps to the
  nearest bucket of a fixed geometric ladder (``growth`` steps, default
  ~sqrt(2) — the MACE data-distribution study's padding/recompile
  trade-off, arXiv:2504.10700). Right for a SERVING stream of many
  different systems: a request's shapes depend only on its own sizes, so
  any stream drawn from a bounded size range hits at most
  ``ceil(log_growth(spread))`` distinct shapes per dimension — a small,
  fixed executable set — instead of one compile per novel size.
"""

from __future__ import annotations

import math
import threading


def round_capacity(n: int, slack: float = 1.2, multiple: int = 128) -> int:
    """Round ``n * slack`` up to a multiple (default 128 = TPU lane width)."""
    if n <= 0:
        return multiple
    target = int(n * slack) + 1
    return ((target + multiple - 1) // multiple) * multiple


def geometric_bucket(n: int, base: int = 128, growth: float = 2.0 ** 0.5,
                     multiple: int = 128) -> int:
    """Smallest ladder rung ``base * growth**k`` (k >= 0) holding ``n``,
    rounded up to ``multiple`` (TPU lane width).

    Lane rounding may collapse adjacent rungs onto the same value (which
    only shrinks the bucket set), so the number of distinct buckets over a
    size range [lo, hi] is bounded by
    ``ceil(log_growth(hi / max(lo, base)))`` + 1 regardless of how many
    distinct sizes the stream contains.
    """
    if growth <= 1.0:
        raise ValueError(f"growth must be > 1, got {growth}")
    if n <= base:
        rung = base
    else:
        k = math.ceil(math.log(n / base) / math.log(growth) - 1e-9)
        rung = base * growth ** k
        # float rounding may land one rung short for exact powers
        if rung < n - 1e-6:
            rung = base * growth ** (k + 1)
    return ((int(math.ceil(rung)) + multiple - 1) // multiple) * multiple


# key of a structure's largest in-degree in a dict of per-structure needs
# (train/data.structure_needs): a census number beside the capacities' names,
# not a capacity (the ``lines`` capacity is frozen from it)
LINE_SLOTS = "line_slots"


def line_table_cap(policy, slots_needed: int, bond_rows: int,
                   b_cap: int) -> int:
    """The capacity ``lines`` of a slot-major in-line table
    (``partition/graph.line_table``), in whole slabs: the table is
    ``cap // b_cap`` slabs of ``b_cap`` slots, and has to hold
    ``slots_needed`` (the largest number of in-lines of one bond).

    Asked for as ``slots_needed * bond_rows`` (the bond rows the partition
    computes), which a mix that fixes both capacities holds to be enough;
    where the policy's answer divides to fewer slabs (``b_cap`` rounded
    further up than ``lines``) it is asked again for whole slabs. Every
    policy here rounds ``bonds`` to a multiple of 128 rows: a slab is then
    whole (8, 128) tiles and the ``(slabs, b_cap, C)`` view of a table's
    rows is a bitcast on the chip. Keep ``multiple`` a multiple of 128.

    The bonds need not be short: with a bond cutoff equal to the cutoff
    (DimeNet++) every built edge is a bond and ``slots_needed`` is the
    largest in-degree less one (51 in the fcc lattice at 5.5 A, against 11
    for CHGNet's 3.5 A bonds), so the table is a line graph over every
    edge, some 18 times CHGNet's.
    """
    cap = policy.get("lines", slots_needed * bond_rows)
    if cap < slots_needed * b_cap:
        cap = policy.get("lines", slots_needed * b_cap)
    return cap - cap % b_cap if b_cap else 0


class FixedCaps:
    """Capacity policy that returns PRECOMPUTED values, ignoring ``needed``.

    The mesh packer builds every batch shard's graph with identical static
    shapes: it first computes the worst-case need per capacity name across
    ALL shards, quantizes once through the real policy, and then hands each
    shard build a ``FixedCaps`` so no shard can land on a different rung.
    Unknown names fall back to the wrapped policy (defensive — all names
    are precomputed in practice).
    """

    def __init__(self, caps: dict[str, int], fallback=None):
        self._caps = dict(caps)
        self._fallback = fallback

    def get(self, name: str, needed: int) -> int:
        cap = self._caps.get(name)
        if cap is None:
            if self._fallback is None:
                raise KeyError(
                    f"FixedCaps has no precomputed capacity {name!r} "
                    f"(have {sorted(self._caps)}) and no fallback policy")
            cap = self._fallback.get(name, needed)
            self._caps[name] = cap  # stay consistent across shards
        if needed > cap:
            raise ValueError(
                f"FixedCaps[{name!r}] = {cap} cannot hold {needed} — the "
                f"precomputed cross-shard maximum was wrong")
        return cap

    def as_dict(self) -> dict[str, int]:
        """The precomputed capacities (a copy) — the analytic planners
        (train/packing.py, tools/pack_audit.py) price tiers and predict
        waste from these without building a graph."""
        return dict(self._caps)

    def fingerprint(self) -> str:
        """Stable id of the FROZEN capacity set: two equal fingerprints
        pack onto byte-identical static shapes (the per-tier analogue of
        ``BucketPolicy.fingerprint``)."""
        return "fixed:" + ",".join(
            f"{k}={v}" for k, v in sorted(self._caps.items()))


def fixed_caps_for_batches(per_structure_needs, batch_size: int,
                           policy=None) -> FixedCaps:
    """Worst-case-stable capacities for micro-batches drawn from a KNOWN
    population (the training regime: the dataset is enumerable up front,
    unlike a serving stream).

    ``per_structure_needs`` is one dict per structure ({"nodes": n,
    "edges": e, ...}); the worst case any ``batch_size``-subset can need is
    the sum of the top-``batch_size`` values per name. That bound is
    quantized ONCE through ``policy`` (default: a fresh ``BucketPolicy``)
    and frozen into a :class:`FixedCaps` — every pack of every shuffled
    epoch then lands on IDENTICAL static shapes, so a whole training run
    compiles exactly one step executable per accumulation window
    (train/data.PackedBatchLoader builds its packs through this).
    """
    if not per_structure_needs:
        raise ValueError("fixed_caps_for_batches needs at least one "
                         "structure's capacity needs")
    batch_size = max(int(batch_size), 1)
    policy = policy or BucketPolicy()
    names = set()
    for need in per_structure_needs:
        names.update(need)
    worst = {}
    for name in names - {LINE_SLOTS}:
        vals = sorted((int(n.get(name, 0)) for n in per_structure_needs),
                      reverse=True)
        worst[name] = sum(vals[:batch_size])
    slots = max(int(n.get(LINE_SLOTS, 0)) for n in per_structure_needs)
    return FixedCaps(freeze_caps(policy, worst, slots), fallback=policy)


def freeze_caps(policy, worst: dict, line_slots: int = 0) -> dict:
    """Quantize the worst need of each capacity ONCE through ``policy``
    (0 stays 0). With ``line_slots`` (the largest in-degree any pack can
    hold) ``lines`` is the in-line table's capacity over the frozen
    ``bonds`` (:func:`line_table_cap`), not the sum of live lines."""
    caps = {name: (policy.get(name, need) if need else 0)
            for name, need in sorted(worst.items())
            if not (name == "lines" and line_slots)}
    if line_slots:
        caps["lines"] = line_table_cap(
            policy, line_slots, worst.get("bond_map", 0), caps["bonds"])
    return caps


class CapacityPolicy:
    """Sticky capacities: grow in buckets, never shrink (per process).

    Thread-safe: DistPotential's background prefetch can build a graph
    concurrently with a synchronous build (an abandoned stale prefetch);
    an unlocked read-modify-write could store a SMALLER cap than a
    concurrent build already used, breaking the never-shrink invariant
    and triggering spurious recompiles."""

    def __init__(self, slack: float = 1.2, multiple: int = 128):
        self.slack = slack
        self.multiple = multiple
        self._caps: dict[str, int] = {}
        self._lock = threading.Lock()

    def get(self, name: str, needed: int) -> int:
        with self._lock:
            cap = self._caps.get(name, 0)
            if needed > cap:
                cap = max(round_capacity(needed, self.slack, self.multiple),
                          cap)
                self._caps[name] = cap
            return cap

    def fingerprint(self) -> str:
        """Configuration id (see ``BucketPolicy.fingerprint``). Sticky
        policies are history-DEPENDENT — two equal fingerprints only
        guarantee shape agreement from a cold start — so AOT-cache
        consumers should prefer the stateless ``BucketPolicy``; the
        fingerprint still distinguishes slack/multiple retunes."""
        return f"sticky:s{self.slack:.6g}:m{self.multiple}"


class BucketPolicy:
    """Stateless geometric capacity ladder (see module docstring).

    Unlike ``CapacityPolicy``, ``get`` is a pure function of ``needed`` —
    no history — so identical request sizes always produce identical
    shapes, and a bounded size range produces a bounded shape set. Small
    dimensions (batch slots) use ``base=1, multiple=1`` via
    :meth:`get_small` so a 3-structure batch doesn't pad to 128 slots.

    The policy additionally carries the memory-aware autobatching bytes
    model: :meth:`calibrate_bytes` records the static HBM planner's
    per-device peak estimate per node rung (BatchedPotential feeds it on
    every fresh compile), and :meth:`estimate_batch_bytes` answers "how
    many bytes would a batch of N total atoms cost" for the scheduler's
    bytes-budget fill (``serve.scheduler.plan_batch``). Shapes remain
    history-free; only BYTES estimates learn.
    """

    def __init__(self, base: int = 128, growth: float = 2.0 ** 0.5,
                 multiple: int = 128):
        if growth <= 1.0:
            raise ValueError(f"growth must be > 1, got {growth}")
        self.base = int(base)
        self.growth = float(growth)
        self.multiple = int(multiple)
        # memory-aware autobatching: per-device peak-byte calibration per
        # node-capacity rung, fed by the static HBM planner
        # (analysis/memory.analyze_memory) each time a new shape bucket
        # compiles. The ladder itself stays stateless — this cache only
        # refines BYTES estimates, never shapes.
        self._bytes_by_cap: dict[int, int] = {}
        self._bytes_lock = threading.Lock()

    def get(self, name: str, needed: int) -> int:
        return geometric_bucket(needed, self.base, self.growth, self.multiple)

    def fingerprint(self) -> str:
        """Stable id of the LADDER CONFIGURATION (not its state): two
        policies with equal fingerprints quantize every request onto
        identical capacity rungs, so a compiled executable keyed on a
        ``bucket_key`` under one policy is exactly reusable under the
        other. The fleet's AOT executable cache folds this into its disk
        key — a retuned ladder (different base/growth/multiple) changes
        every padded shape and must miss, not deserialize a stale
        program."""
        return f"bucket:b{self.base}:g{self.growth:.6g}:m{self.multiple}"

    # ---- bytes-per-structure model (memory-aware autobatching) ----

    def calibrate_bytes(self, node_cap: int, peak_bytes: int) -> None:
        """Record the analyzer's estimated per-device peak for a batch
        program whose node-capacity rung is ``node_cap``. Keeps the WORST
        observed peak per rung (edge-heavy packs of the same rung must not
        shrink the estimate)."""
        node_cap, peak_bytes = int(node_cap), int(peak_bytes)
        if node_cap <= 0 or peak_bytes <= 0:
            return
        with self._bytes_lock:
            prev = self._bytes_by_cap.get(node_cap, 0)
            if peak_bytes > prev:
                self._bytes_by_cap[node_cap] = peak_bytes

    def bytes_calibrated(self) -> bool:
        with self._bytes_lock:
            return bool(self._bytes_by_cap)

    def has_calibrated_rung(self, total_atoms: int) -> bool:
        """Whether ``total_atoms``'s own node rung has a MEASURED peak
        (vs an extrapolated guess). Hard admission decisions key on this:
        rejecting on extrapolation would livelock a lane whose first
        calibration happened to land over budget — nothing would ever be
        admitted to compile the rung and correct the guess."""
        cap = self.get("nodes", max(int(total_atoms), 1))
        with self._bytes_lock:
            return cap in self._bytes_by_cap

    def estimate_batch_bytes(self, total_atoms: int) -> int | None:
        """Estimated per-device peak bytes of a batch totalling
        ``total_atoms`` atoms: the calibrated peak of its node rung when
        that exact rung has compiled before; otherwise an estimate that
        errs UP — over-admitting is the failure mode that OOMs. With two
        or more calibrated rungs, an affine fit ``resident + k * cap``
        through the extreme rungs (a program's peak has a batch-size-
        independent resident term — params, consts — that a pure
        bytes-per-atom slope would wrongly scale away on SMALL batches);
        with one rung, linear scaling up and the observed peak as a hard
        floor below it (a never-compiled small batch is assumed no
        cheaper than the cheapest batch ever measured — conservative by
        design; its own first compile replaces the guess with the exact
        rung). None until any calibration exists (callers then skip the
        budget check rather than trust a made-up constant)."""
        cap = self.get("nodes", max(int(total_atoms), 1))
        with self._bytes_lock:
            exact = self._bytes_by_cap.get(cap)
            if exact is not None:
                # same floor as the fit path: a lightly-calibrated rung
                # never estimates below a peak already OBSERVED at a
                # smaller rung (an edge-heavy smaller pack bounds it)
                return max(b for c, b in self._bytes_by_cap.items()
                           if c <= cap)
            if not self._bytes_by_cap:
                return None
            pts = sorted(self._bytes_by_cap.items())
            floor = min(b for _, b in pts)
            if len(pts) >= 2:
                (c_lo, b_lo), (c_hi, b_hi) = pts[0], pts[-1]
                k = max((b_hi - b_lo) / max(c_hi - c_lo, 1), 0.0)
                resident = max(b_lo - k * c_lo, 0.0)
                est = int(resident + k * cap) + 1
                # the fit runs through the EXTREME rungs only — never
                # estimate below a peak already OBSERVED at a smaller
                # rung (an edge-heavy middle rung would otherwise admit
                # a bigger batch as cheaper than its measured smaller
                # sibling)
                observed = [b for c, b in pts if c <= cap]
                return max(est, *observed) if observed else est
            coeff = max(b / c for c, b in pts)
        return max(int(cap * coeff) + 1, floor)

    def get_small(self, needed: int) -> int:
        """Bucket for small count dimensions (e.g. batch size): next power
        of two, no lane-width rounding."""
        n = max(int(needed), 1)
        return 1 << (n - 1).bit_length()

    def max_rungs(self, lo: int, hi: int) -> int:
        """Upper bound on distinct ladder rungs a stream of sizes in
        ``[lo, hi]`` can touch (lane rounding only collapses rungs). The
        serving/adversarial compile-bound tests assert executable counts
        against this."""
        lo = max(int(lo), 1)
        hi = max(int(hi), lo)
        spread = hi / max(lo, self.base)
        if spread <= 1.0:
            return 1
        return int(math.ceil(math.log(spread) / math.log(self.growth))) + 1

    def ladder_bound(self, lo_total: int, hi_total: int,
                     max_batch: int) -> int:
        """Generous-but-logarithmic bound on the executables a serving
        stream whose batch atom-totals span ``[lo_total, hi_total]`` can
        compile: node and edge ladders each contribute at most
        ``max_rungs`` rungs (edge counts track atom counts within a
        constant factor, costing at most a constant number of extra rungs
        — folded into the +2), crossed with the batch-slot powers of two
        in play. The single source of truth for the load-test ``--check``
        gate and the adversarial-stream tests."""
        rungs = self.max_rungs(lo_total, hi_total)
        b_slots = len({self.get_small(b)
                       for b in range(1, max(int(max_batch), 1) + 1)})
        return (2 * rungs + 2) * b_slots
