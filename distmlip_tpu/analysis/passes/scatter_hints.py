"""Scatter-hint pass: hot-path segment sums must declare sorted indices.

The edge layout exists to serve one hint: partition/graph.py and
partition/batch.py emit every edge array dst-sorted (nondecreasing
``edge_dst`` per segment, repeat-last-real padding) precisely so every
``segment_sum``/scatter-add on the hot path can pass
``indices_are_sorted=True`` and take the TPU scatter fast path. (CHGNet's
lines need no hint: their sum onto bonds is a sum over the slabs of the
in-line table, ``ops/segment.slab_sum``, not a scatter.) A call
site that forgets the hint silently falls back to the general scatter —
correct results, order-of-magnitude slower — which no numeric test will
ever catch. This pass makes the hint a statically checked contract.

Scope: ``requires = {"forward"}``. The *transpose* of an unsorted gather
(``positions[src]``) in a grad program is legitimately an unsorted
scatter-add — src order is not dst order — so the contract is stated on
the forward (hot-path) program, where every scatter-add IS a segment
reduction over a dst-sorted layout.

- ERROR: forward-program ``scatter-add`` with ``indices_are_sorted=False``
  (suppress audited exceptions with ``# contract: allow(scatter_hints)``
  on the call-site line).
- INFO: other scatter variants (scatter-max in segment softmax etc.)
  missing the hint — slower, but not on the per-edge aggregation path.
"""

from __future__ import annotations

from .. import ir
from . import ContractPass, Program, Severity, register


@register
class ScatterHintsPass(ContractPass):
    name = "scatter_hints"
    description = ("forward-program scatter-adds must carry "
                   "indices_are_sorted=True (dst-sorted layout contract)")
    requires = frozenset({"forward"})

    def run(self, program: Program) -> list:
        findings = []
        for site in ir.iter_sites(program.jaxpr):
            prim = site.primitive
            if prim not in ir.SCATTER_PRIMS:
                continue
            hint = site.eqn.params.get("indices_are_sorted")
            if hint is None:
                # a jax version renaming the param must fail LOUDLY — a
                # default of "hinted" would disable this gate vacuously
                findings.append(self.finding(
                    Severity.ERROR,
                    f"{prim} eqn carries no indices_are_sorted param — "
                    "jax renamed it? update analysis/passes/scatter_hints "
                    "(silence gates must never pass vacuously)",
                    site=site, rule="no-hint-param"))
                continue
            if hint:
                continue
            if prim == "scatter-add":
                findings.append(self.finding(
                    Severity.ERROR,
                    "scatter-add without indices_are_sorted=True on the "
                    "forward path — the dst-sorted layout guarantees the "
                    "hint; pass it through (ops/segment.py) or audit with "
                    "# contract: allow(scatter_hints)", site=site,
                    rule="unhinted-add"))
            else:
                findings.append(self.finding(
                    Severity.INFO,
                    f"{prim} without indices_are_sorted hint", site=site,
                    rule="unhinted-other"))
        return findings
