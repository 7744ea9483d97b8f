"""The stages of an energy-and-forces step, and which compiled instruction
belongs to which.

The models wrap each stage in ``telemetry.scope(<stage>)``
(``jax.named_scope``: metadata only). XLA keeps the scope stack in every
instruction's ``op_name``, also through ``jvp``, ``transpose`` and remat,
so the text of the COMPILED step says where each device operation came
from: :func:`stage_table` reads it into plain rows, one per instruction
that can show up as a device event. A stage is the innermost declared name
on an operation's scope stack.
"""

from __future__ import annotations

import re
from collections import Counter

# one tuple for every model family, so that one metric reads them all
STAGES = (
    "edge_geometry",    # edge vectors, lengths, envelope, radial rows, Y_lm
    "edge_gather",      # per-edge rows into chunk order, and the scan's slices
    "radial_mlp",       # radial functions through their MLP / linears
    "edge_message",     # per-edge tensor products, incl. the src-row gather
    "edge_rotation",    # Wigner blocks; features into and out of the edge frame
    "edge_aggregate",   # segment sum onto dst, and the accumulate around it
    "expert_mix",       # per-system gate, softmax, expert weights merged
    "node_linear",      # channel-mixing linears on nodes
    "node_tensor",      # symmetric contraction / rank-2 node products
    "node_gate",        # equivariant gate: scalars activated, l > 0 scaled
    "readout",          # per-atom energies, scale and shift
    "pair_repulsion",   # ZBL
    "halo",             # exchange between partitions (parallel/halo.py)
    # the bond (line) graph of a three-body model: bonds are its nodes,
    # ordered pairs of bonds that share a centre atom its edges (lines)
    "line_geometry",    # bond-node geometry, three-body basis, theta, Fourier
    "line_message",     # three gathers, gated MLP, sum onto the dst bond
    "angle_update",     # the angle feature of every line
    "bond_map",         # edge rows onto bond nodes and back
    # directional message passing (DimeNet++): the hidden state lives on
    # every bond, triplets k -> j -> i are the lines into bond j -> i
    "triplet_basis",    # line geometry, cos theta, j_l, P_l, envelope, rows
    "triplet_message",  # the slab scan: s[line_src] x basis, summed onto bonds
    "edge_update",      # embedding block and every per-bond linear
)
# scopes parallel/halo.py has carried since PR 1; they ARE the halo stage,
# and its two index remaps the bond_map stage
_STAGE_OF_SCOPE = {
    **{s: s for s in STAGES}, "halo_exchange": "halo",
    "bond_halo_exchange": "halo", "halo_exchange_all": "halo",
    "edge_to_bond": "bond_map", "bond_to_edge": "bond_map"}

# jit(name) is a function's name, not a scope; jvp( transpose( vmap( ... wrap
# scope stacks and may close many components later
_FUNCTION = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER = re.compile(r"\b\w+\(|\)")


def stage_of(op_name: str) -> str | None:
    """The innermost declared stage on a scope stack, given as XLA's
    ``op_name`` (``jit(f)/transpose(jvp(a/b))/c/mul``) or as a jaxpr
    equation's name stack."""
    flat = _WRAPPER.sub("", _FUNCTION.sub("", op_name))
    for part in reversed(flat.split("/")):
        stage = _STAGE_OF_SCOPE.get(part)
        if stage is not None:
            return stage
    return None


def pass_of(op_name: str) -> str:
    """``recompute`` for what a checkpoint runs again inside the backward
    pass, ``backward`` for the rest under a ``transpose(``, else
    ``forward``."""
    if "transpose(" not in op_name:
        return "forward"
    return "recompute" if "rematted_computation" in op_name else "backward"


# ---- the compiled module's text ----
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_LOOP = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="[^"]*"')
_OPERAND = re.compile(r"%([\w.\-]+)")
# never a device event of their own
_SILENT = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


def _split_type(rest: str) -> tuple[str, str]:
    """(result type, the remainder) of an instruction's text after `` = ``;
    a tuple type holds spaces, so its parentheses are matched."""
    if not rest.startswith("("):
        head, _, tail = rest.partition(" ")
        return head, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i + 1], rest[i + 1:].lstrip()
    return rest, ""


def stage_table(hlo_text: str) -> list[dict]:
    """One row per instruction of ``hlo_text`` (``Compiled.as_text()``)
    outside the fused computations: ``head`` (``%name = type opcode``, plus
    the custom-call target: what a profiler's device event is named by),
    ``stage`` (or None), ``pass``, and for a fusion ``stages``: every stage
    among its fused instructions. A fusion's stage is its root's (XLA hands
    the root's metadata to the fusion); where the root carries none and the
    fused instructions agree on one stage, that one. An instruction with no
    metadata at all is the compiler's own (a copy into another layout, an
    asynchronous copy or slice, their concatenation, an update written into
    a buffer a loop carries): it takes stage and pass of the instruction
    that made its first operand, or of the first operand that has a stage
    where the first has none; failing that, of the ``while`` whose body or
    condition it sits in (a scatter over a few indices becomes a loop of
    whole-array updates whose body has no metadata); and says so
    (``inherited``). Whatever still reads ``forward`` inside a ``while``
    that reads ``backward`` or ``recompute`` runs when that loop runs: its
    pass is ``recompute`` (``pass_inherited``). A checkpointed scan body's
    writes of its pieces into the chunk's array carry no
    ``rematted_computation`` of their own (116.5 ms a step of MACE's,
    PERF.md section 5, PR 35)."""
    computations: dict[str, list] = {}  # name -> [(head, opcode, ...)]
    fused_bodies = set()
    loops = {}  # a while's body or condition -> (its op_name, where it is)
    body = name = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                name = c.group(1)
                body = computations.setdefault(name, [])
            continue
        if body is None:
            continue
        result, tail = _split_type(line[m.end():])
        opcode = tail.partition("(")[0].strip()
        op = _OP_NAME.search(line)
        if opcode == "while":
            for loop in _LOOP.findall(line):
                loops[loop] = (op.group(1) if op else "", name)
        called = _FUSED.search(line) if opcode == "fusion" else None
        if called is not None:
            fused_bodies.add(called.group(1))
        target = _TARGET.search(line) if opcode == "custom-call" else None
        head = f"%{m.group(1)} = {result} {opcode}"
        operands = _OPERAND.findall(
            tail.partition("(")[2].partition(")")[0])
        body.append((head + (" " + target.group(0) if target else ""),
                     opcode, op.group(1) if op else "",
                     called.group(1) if called else None,
                     m.group(1), operands))

    def fused_stages(name: str, seen: frozenset) -> Counter:
        found: Counter = Counter()
        for _, _, op_name, called, *_ in computations.get(name, ()):
            stage = stage_of(op_name)
            if stage is not None:
                found[stage] += 1
            if called is not None and called not in seen:
                found += fused_stages(called, seen | {called})
        return found

    def loop_label(name: str):
        """(stage, pass) of the nearest enclosing ``while`` that has a
        stage: the compiler expands a scatter into a loop whose body
        carries no metadata, the loop itself does."""
        seen = set()
        while name in loops and name not in seen:
            seen.add(name)
            op_name, name = loops[name]
            stage = stage_of(op_name)
            if stage is not None:
                return stage, pass_of(op_name)
        return None

    def loop_pass(name: str):
        """The pass of the nearest enclosing ``while`` that does not read
        ``forward``, or None."""
        seen = set()
        while name in loops and name not in seen:
            seen.add(name)
            op_name, name = loops[name]
            if pass_of(op_name) != "forward":
                return pass_of(op_name)
        return None

    rows = []
    for name, instructions in computations.items():
        if name in fused_bodies:
            continue
        runs_in = loop_pass(name)
        made = {}  # instruction name -> its row, in the computation's order
        for head, opcode, op_name, called, own, operands in instructions:
            row = {"head": head, "stage": stage_of(op_name),
                   "pass": pass_of(op_name)}
            if called is not None:
                inside = fused_stages(called, frozenset({called}))
                row["stages"] = sorted(inside)
                if row["stage"] is None and len(inside) == 1:
                    row["stage"] = row["stages"][0]
            if not op_name and called is None:
                sources = [made[o] for o in operands if o in made]
                # the first operand that has a stage (an update written
                # into a carried buffer has it second), else the first
                source = next((r for r in sources if r["stage"]),
                              sources[0] if sources else None)
                if source is not None:
                    row.update(stage=source["stage"], inherited=True)
                    row["pass"] = source["pass"]
            if not op_name and row["stage"] is None:
                label = loop_label(name)
                if label is not None:
                    row.update(stage=label[0], inherited=True)
                    row["pass"] = label[1]
            if runs_in is not None and row["pass"] == "forward":
                row.update({"pass": "recompute", "pass_inherited": True})
            made[own] = row
            if opcode not in _SILENT:
                rows.append(row)
    return rows
