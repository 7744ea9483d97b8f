"""Single jaxpr walker shared by every contract pass.

One recursion (into ``pjit`` / ``scan`` / ``while`` / ``cond`` / ``remat``
/ ``shard_map`` / custom-derivative sub-jaxprs) serving collective
counting, host-sync detection, dtype walks, scatter-hint checks, const
inspection and liveness — so each new invariant is a pass over
:func:`iter_sites`, not another hand-rolled tree walk.
``distmlip_tpu.parallel.audit`` is a thin compatibility shim over this
module.

Every yielded :class:`EqnSite` carries the eqn itself plus *where it is*:
the stack of enclosing control-flow primitive names (``("pjit", "while")``
— the host-sync pass keys its "inside the MD while_loop" escalation off
this), the ``jax.named_scope`` name stack (source metadata, best effort),
and the owning (sub)jaxpr so local dataflow (liveness) stays computable.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Any, Iterator

# collective primitives the graph runtime can emit (names as they appear
# in jaxprs across the jax versions this repo supports)
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "psum", "psum_invariant", "all_gather", "all_to_all",
    "reduce_scatter", "pmax", "pmin", "pgather", "collective_permute",
})

# the ring-shift permute primitive's names across jax versions — count both
# wherever a gate compares ppermute counts, or the parity check passes
# vacuously (0 == 0) on a build emitting the other name
PPERMUTE_PRIMS = ("ppermute", "collective_permute")


def ppermute_count(counts) -> int:
    """Ring-permute occurrences in a ``{primitive: count}`` mapping,
    whatever the primitive is called on this jax build."""
    return sum(int(counts.get(p, 0)) for p in PPERMUTE_PRIMS)

# host-synchronizing primitives: anything that stalls the device on the
# host mid-program. Substring matching on "callback" keeps this robust
# across jax versions' primitive renames (pure_callback/io_callback/
# debug_callback all match).
HOST_SYNC_MARKERS = ("callback", "infeed", "outfeed")
HOST_SYNC_EXACT = frozenset({"host_local_array_to_global_array",
                             "debug_print"})

# scatter variants that carry the ``indices_are_sorted`` hint
SCATTER_PRIMS = frozenset({
    "scatter-add", "scatter", "scatter-mul", "scatter-min", "scatter-max",
    "scatter-apply",
})


@dataclass
class EqnSite:
    """One eqn plus its position in the traced program."""

    eqn: Any
    path: tuple          # enclosing control-flow primitive names, outer first
    scope: str           # jax.named_scope stack ("" when metadata is absent)
    jaxpr: Any           # the (sub)jaxpr owning this eqn
    # scope stacks of the enclosing eqns (a sub-jaxpr's eqns carry only
    # what was entered inside it), outer first, then ``scope``
    stack: str = ""

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name


def sub_jaxprs(params, unwrap: bool = True) -> list:
    """Collect Jaxpr values from an eqn's params — fallback for jax
    versions without ``jax.core.jaxprs_in_params``. ``unwrap=True`` (the
    walker's view) reduces ClosedJaxpr to its Jaxpr; ``unwrap=False``
    preserves ClosedJaxpr wrappers so their ``consts`` stay reachable
    (:func:`program_consts`)."""
    out = []

    def visit(v):
        if hasattr(v, "eqns"):           # Jaxpr
            out.append(v)
        elif hasattr(v, "jaxpr"):        # ClosedJaxpr
            out.append(v.jaxpr if unwrap else v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                visit(x)

    for v in params.values():
        visit(v)
    return out


def scope_of(eqn) -> str:
    """named_scope stack string (best effort: source metadata may be absent
    on some jax builds)."""
    try:
        return str(eqn.source_info.name_stack)
    except Exception:  # noqa: BLE001 - metadata is optional
        return ""


def source_location(eqn):
    """(file, line) of the user frame the eqn was traced from, or None
    when the traceback holds no user frame (jax's private
    source_info_util: the only place that knows which frames are jax's)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None
    return (frame.file_name, int(frame.start_line))


def iter_sites(closed_jaxpr) -> Iterator[EqnSite]:
    """Yield an :class:`EqnSite` for every eqn in the program, recursing
    into all nested sub-jaxprs. Loop/branch bodies are visited ONCE per
    trace — multiply by trip count yourself for dynamic totals."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    yield from _walk(jaxpr, ())


def _walk(jaxpr, path, outer: str = "") -> Iterator[EqnSite]:
    for eqn in jaxpr.eqns:
        scope = scope_of(eqn)
        stack = f"{outer}/{scope}" if outer and scope else outer or scope
        yield EqnSite(eqn=eqn, path=path, scope=scope, jaxpr=jaxpr,
                      stack=stack)
        subs = sub_jaxprs(eqn.params)
        if subs:
            sub_path = path + (eqn.primitive.name,)
            for sub in subs:
                yield from _walk(sub, sub_path, stack)


def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs (legacy surface of
    parallel/audit.py; prefer :func:`iter_sites` in new code)."""
    for site in _walk(getattr(jaxpr, "jaxpr", jaxpr), ()):
        yield site.eqn


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def eqn_axis_names(eqn) -> tuple:
    """Mesh axis names a collective eqn operates over, from its params.

    Collective primitives carry the axis under different param names across
    primitives and jax versions (``axis_name`` for ppermute/all_gather,
    ``axes`` for psum/pmax, sometimes ``axis_index_groups`` alongside);
    values may be a single name or a tuple. Returns ``("<unknown>",)`` when
    no axis metadata is present.
    """
    for key in ("axis_name", "axes", "named_axes"):
        val = eqn.params.get(key)
        if val is None:
            continue
        if isinstance(val, (tuple, list, frozenset, set)):
            named = tuple(v for v in val if isinstance(v, (str, int)))
            if named or not val:
                # an EMPTY axes tuple is a no-op psum (identity) the
                # partial evaluator sometimes leaves behind — attribute it
                # to no axis. A NON-empty tuple of unparseable axis objects
                # must NOT vanish: fall through to "<unknown>" so silence
                # gates fail loudly instead of vacuously.
                return named
        elif isinstance(val, (str, int)):
            return (val,)
        break
    return ("<unknown>",)


def count_collectives(closed_jaxpr) -> Counter:
    """Counter of collective primitive name -> occurrence count over the
    whole program (nested jaxprs included)."""
    counts: Counter = Counter()
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            counts[name] += 1
    return counts


def collectives_by_axis(closed_jaxpr) -> dict:
    """``{axis_name: Counter(primitive -> count)}`` over the whole program.
    A collective naming several axes counts against each."""
    by_axis: dict[str, Counter] = {}
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            continue
        for ax in eqn_axis_names(eqn):
            by_axis.setdefault(str(ax), Counter())[name] += 1
    return by_axis


def count_primitives(closed_jaxpr, names) -> Counter:
    """Occurrences of specific primitive names (nested jaxprs included)."""
    names = frozenset(names)
    counts: Counter = Counter()
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name in names:
            counts[eqn.primitive.name] += 1
    return counts


def is_host_sync(primitive_name: str) -> bool:
    return (primitive_name in HOST_SYNC_EXACT
            or any(m in primitive_name for m in HOST_SYNC_MARKERS))


# ---------------------------------------------------------------------------
# consts
# ---------------------------------------------------------------------------

def program_consts(closed_jaxpr) -> list:
    """[(value, aval)] of every constant baked into the traced program.

    Top-level ClosedJaxpr consts are the interesting ones (make_jaxpr
    hoists closure values there); nested ClosedJaxprs found in params are
    included too when they carry consts of their own.
    """
    out = []
    seen: set[int] = set()

    def collect(cj):
        if id(cj) in seen:
            return
        seen.add(id(cj))
        consts = getattr(cj, "consts", None)
        jaxpr = getattr(cj, "jaxpr", None)
        if consts and jaxpr is not None:
            for var, val in zip(jaxpr.constvars, consts):
                out.append((val, var.aval))
        if jaxpr is None:
            jaxpr = cj
        for eqn in jaxpr.eqns:
            for sub in sub_jaxprs(eqn.params, unwrap=False):
                collect(sub)

    collect(closed_jaxpr)
    return out


# ---------------------------------------------------------------------------
# liveness (per-jaxpr dead-compute detection)
# ---------------------------------------------------------------------------

def dead_eqns(jaxpr) -> list:
    """Eqns of ONE (sub)jaxpr with no dataflow path to its outputs.

    Local to the given jaxpr (callers recurse via :func:`iter_sites` /
    ``sub_jaxprs``): an eqn is live iff any of its outvars feeds the
    jaxpr's outvars transitively, or it has side effects. DropVar outputs
    (jax's own `_:` binders) count as unused.
    """
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    live: set[int] = set()
    for v in jaxpr.outvars:
        if not _is_literal(v):
            live.add(id(v))
    dead = []
    for eqn in reversed(jaxpr.eqns):
        out_live = any(id(v) in live for v in eqn.outvars)
        if out_live or _has_effects(eqn):
            for v in eqn.invars:
                if not _is_literal(v):
                    live.add(id(v))
        else:
            dead.append(eqn)
    dead.reverse()
    return dead


def _is_literal(v) -> bool:
    return hasattr(v, "val") and not hasattr(v, "count")


def _has_effects(eqn) -> bool:
    """True for eqns with REAL side effects (callbacks, io). NamedAxisEffect
    is axis bookkeeping shard_map attaches to every collective — a psum
    with an unused result is still dead compute, so it does not count."""
    try:
        return any("NamedAxis" not in type(e).__name__ for e in eqn.effects)
    except Exception:  # noqa: BLE001 - older jax: no effects attr
        return False
