#!/usr/bin/env python
"""Audit collective counts of the graph-parallel potential programs.

    python tools/halo_audit.py [--model chgnet|pair|tensornet]
        [--nparts 2] [--reps 4,2,2] [--batch B] [--mesh B,S]
        [--per-scope] [--json]

Builds a small test system, traces the jitted potential (plus the
fused-aux program when the model has a sitewise head), and prints
collective counts straight from the jaxprs — the chip-free view of what the
halo exchange costs per MD step. ``--per-scope`` additionally groups
ppermutes by ``jax.named_scope`` name stack so the per-layer structure is
visible.

``--batch B`` additionally packs B jittered copies of the system into a
block-diagonal batched graph (partition.pack_structures) and traces the
batched potential at batch sizes 1 and B: collective counts MUST be
independent of B (the batched engine is single-partition by design — a
batch adds zero communication). A violation exits 3.

``--mesh B,S`` traces the 2-D mesh batched potential at the (batch=B,
spatial=S) placement and attributes every collective to its mesh axis:
the BATCH axis must carry ZERO collectives (block-diagonal batches need
no cross-batch traffic), and at S > 1 the spatial-axis ppermute count
must MATCH the 1-D graph-parallel ring at P=S (packing adds structures,
not communication). A violation exits 3.

Exit codes: 0 ok, 2 usage, 3 invariant violated (batched counts depend
on B, batch-axis collectives, or spatial ppermute mismatch).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# multi-device CPU mesh, set before jax initializes (same trick as tests)
_flag = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()


def build_system(reps, model_name):
    import numpy as np

    from distmlip_tpu import geometry

    rng = np.random.default_rng(0)
    a = 3.5
    unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
    frac, lattice = geometry.make_supercell(unit, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, 0.03, (len(frac), 3))
    species = rng.integers(0, 2, len(frac)).astype(np.int32)
    return cart, lattice, species


def make_model(name):
    import jax

    if name == "chgnet":
        from distmlip_tpu.models.chgnet import CHGNet, CHGNetConfig

        model = CHGNet(CHGNetConfig(
            num_species=4, units=16, num_rbf=6, num_blocks=3,
            cutoff=3.2, bond_cutoff=2.6))
        use_bg, bond_r = True, 2.6
    elif name == "tensornet":
        from distmlip_tpu.models.tensornet import TensorNet, TensorNetConfig

        model = TensorNet(TensorNetConfig(
            num_species=4, units=16, num_rbf=8, cutoff=3.2))
        use_bg, bond_r = False, 0.0
    elif name == "pair":
        from distmlip_tpu.models.pair import PairConfig, PairPotential

        model = PairPotential(PairConfig(cutoff=3.2))
        use_bg, bond_r = False, 0.0
    else:
        raise SystemExit(f"unknown --model {name!r}")
    params = model.init(jax.random.PRNGKey(0))
    return model, params, use_bg, bond_r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="halo_audit", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model", default="chgnet",
                    choices=("chgnet", "pair", "tensornet"))
    ap.add_argument("--nparts", type=int, default=2)
    ap.add_argument("--reps", default=None,
                    help="supercell reps gx,gy,gz (default: 2*nparts,2,2 so "
                         "slabs stay wider than the cutoff)")
    ap.add_argument("--batch", type=int, default=0,
                    help="also audit the batched (packed) potential at "
                         "batch sizes 1 and B; counts must not depend on B")
    ap.add_argument("--mesh", default=None,
                    help="B,S: audit the 2-D mesh batched potential at the "
                         "(batch=B, spatial=S) placement — the batch axis "
                         "must carry zero collectives and the spatial "
                         "ppermute count must match the 1-D ring at P=S")
    ap.add_argument("--per-scope", action="store_true")
    ap.add_argument("--json", action="store_true")
    try:
        args = ap.parse_args(argv)
        if args.reps is None:
            reps = (max(2 * args.nparts, 4), 2, 2)
        else:
            reps = tuple(int(x) for x in args.reps.split(","))
        if len(reps) != 3:
            raise ValueError("--reps wants gx,gy,gz")
        mesh_bs = None
        if args.mesh:
            mesh_bs = tuple(int(x) for x in args.mesh.split(","))
            if len(mesh_bs) != 2 or mesh_bs[0] < 1 or mesh_bs[1] < 1:
                raise ValueError("--mesh wants B,S (both >= 1)")
    except (SystemExit, ValueError) as e:
        if isinstance(e, SystemExit) and e.code in (0, None):
            return 0
        print(f"usage error: {e}", file=sys.stderr)
        return 2

    import jax

    jax.config.update("jax_platforms", "cpu")

    from distmlip_tpu.neighbors import neighbor_list_numpy
    from distmlip_tpu.parallel import graph_mesh, make_potential_fn
    from distmlip_tpu.parallel.audit import (count_collectives,
                                             ppermutes_by_scope)
    from distmlip_tpu.partition import build_partitioned_graph, build_plan

    model, params, use_bg, bond_r = make_model(args.model)
    cart, lattice, species = build_system(reps, args.model)
    r = model.cfg.cutoff
    nl = neighbor_list_numpy(cart, lattice, [1, 1, 1], r, bond_r=bond_r)
    plan = build_plan(nl, lattice, [1, 1, 1], args.nparts, r, bond_r, use_bg)
    graph, _host = build_partitioned_graph(plan, nl, species, lattice)
    mesh = graph_mesh(args.nparts) if args.nparts > 1 else None

    programs = {"potential": make_potential_fn(model.energy_fn, mesh)}
    if hasattr(model, "energy_and_aux_fn"):
        programs["potential+aux"] = make_potential_fn(
            model.energy_and_aux_fn, mesh, aux=True)

    report = {"model": args.model, "nparts": args.nparts,
              "n_atoms": len(cart), "e_split": graph.e_split,
              "e_cap": graph.e_cap, "programs": {}}
    for name, fn in programs.items():
        jaxpr = jax.make_jaxpr(fn)(params, graph, graph.positions)
        counts = count_collectives(jaxpr)
        entry = {"total": sum(counts.values()), **dict(counts)}
        if args.per_scope:
            entry["ppermutes_by_scope"] = dict(ppermutes_by_scope(jaxpr))
        report["programs"][name] = entry

    # both gates below run as the registered collective_placement contract
    # pass (distmlip_tpu.analysis) — the CLI only builds Program configs
    # and maps error findings to the historical exit code 3
    from distmlip_tpu.analysis import Program, error_count, get_passes, run_passes

    coll_pass = get_passes(["collective_placement"])

    batch_ok = True
    if args.batch > 0:
        from distmlip_tpu.calculators import Atoms
        from distmlip_tpu.parallel import make_batched_potential_fn
        from distmlip_tpu.partition import pack_structures

        rng = __import__("numpy").random.default_rng(1)
        base = Atoms(numbers=species + 1, positions=cart, cell=lattice)

        def jittered():
            a = base.copy()
            a.positions = a.positions + rng.normal(0, 0.02, a.positions.shape)
            return a

        bfn = make_batched_potential_fn(model.energy_fn)
        ref_total = None
        for B in sorted({1, args.batch}):
            bgraph, _ = pack_structures(
                [jittered() for _ in range(B)], model.cfg.cutoff, bond_r,
                use_bg, species_fn=lambda z: (z - 1).astype("int32"))
            jaxpr = jax.make_jaxpr(bfn)(params, bgraph, bgraph.positions)
            counts = count_collectives(jaxpr)
            total = sum(counts.values())
            # counts must be INDEPENDENT of B: pin every B to the first
            # (smallest) batch's total via the exact-equality gate
            cfg = ({} if ref_total is None
                   else {"expected_total_collectives": ref_total})
            findings = run_passes(
                Program(name=f"batched[B={B}]", jaxpr=jaxpr, config=cfg),
                coll_pass)
            if error_count(findings):
                batch_ok = False
            if ref_total is None:
                ref_total = total
            report["programs"][f"batched[B={B}]"] = {
                "total": total, **dict(counts)}
        report["batched_collectives_independent_of_B"] = batch_ok

    mesh_ok = True
    mesh_detail = ""
    if mesh_bs is not None:
        B_m, S_m = mesh_bs
        from distmlip_tpu.calculators import Atoms
        from distmlip_tpu.parallel import (BATCH_AXIS, SPATIAL_AXIS,
                                           device_mesh, graph_mesh,
                                           make_batched_potential_fn,
                                           make_potential_fn)
        from distmlip_tpu.analysis.ir import ppermute_count
        from distmlip_tpu.parallel.audit import collectives_by_axis
        from distmlip_tpu.partition import build_partitioned_graph as _bpg
        from distmlip_tpu.partition import build_plan as _bp
        from distmlip_tpu.partition import pack_structures

        import numpy as np
        rng = np.random.default_rng(2)
        # the mesh system needs slabs wide enough for S_m spatial parts
        cart_m, lat_m, species_m = build_system(
            (max(2 * S_m, 4), 2, 2), args.model)
        base = Atoms(numbers=species_m + 1, positions=cart_m, cell=lat_m)

        def jittered_m():
            a = base.copy()
            a.positions = a.positions + rng.normal(0, 0.02, a.positions.shape)
            return a

        try:
            mesh = device_mesh(B_m, S_m)
        except ValueError as e:
            # a placement that doesn't fit the host's devices is a usage
            # error (exit 2), not an invariant violation (exit 3)
            print(f"usage: {e}", file=sys.stderr)
            return 2
        bgraph, _ = pack_structures(
            [jittered_m() for _ in range(B_m)], model.cfg.cutoff, bond_r,
            use_bg, species_fn=lambda z: (z - 1).astype("int32"),
            spatial_parts=S_m, batch_parts=B_m)
        bfn_mesh = make_batched_potential_fn(model.energy_fn, mesh=mesh)
        jaxpr_m = jax.make_jaxpr(bfn_mesh)(params, bgraph, bgraph.positions)
        by_axis = {ax: dict(cnt)
                   for ax, cnt in collectives_by_axis(jaxpr_m).items()}
        batch_coll = sum(by_axis.get(BATCH_AXIS, {}).values())
        mesh_pp = ppermute_count(by_axis.get(SPATIAL_AXIS, {}))
        unattributed = sum(by_axis.get("<unknown>", {}).values())
        entry = {"total": sum(sum(c.values()) for c in by_axis.values()),
                 "by_axis": by_axis, "batch_axis_collectives": batch_coll,
                 "spatial_ppermutes": mesh_pp,
                 "unattributed_collectives": unattributed}
        # the 2-D mesh invariants, stated as collective_placement config:
        # ZERO collectives on the batch axis, nothing unattributed (a jax
        # version changing the eqn param names must fail loudly, never
        # pass vacuously), and at S > 1 spatial ppermute parity with the
        # 1-D graph-parallel ring at P=S on ONE copy of the same system
        # (packing adds structures, not communication)
        mesh_cfg = {"forbidden_axes": [BATCH_AXIS],
                    "require_attributed": True}
        if S_m > 1:
            nl_m = neighbor_list_numpy(cart_m, lat_m, [1, 1, 1], r,
                                       bond_r=bond_r)
            plan_m = _bp(nl_m, lat_m, [1, 1, 1], S_m, r, bond_r, use_bg)
            graph_m, _h = _bpg(plan_m, nl_m, species_m, lat_m)
            ring_fn = make_potential_fn(model.energy_fn, graph_mesh(S_m))
            jaxpr_r = jax.make_jaxpr(ring_fn)(params, graph_m,
                                              graph_m.positions)
            ring_axes = collectives_by_axis(jaxpr_r)
            ring_pp = ppermute_count(ring_axes.get(SPATIAL_AXIS, {}))
            entry["ring_ppermutes_1d"] = ring_pp
            mesh_cfg["expected_ppermutes"] = {SPATIAL_AXIS: ring_pp}
            mesh_detail = (f"batch_collectives={batch_coll} "
                           f"spatial_ppermutes={mesh_pp} (1-D ring: "
                           f"{ring_pp})")
        else:
            mesh_detail = f"batch_collectives={batch_coll}"
        mesh_findings = run_passes(
            Program(name=f"mesh[{B_m}x{S_m}]", jaxpr=jaxpr_m,
                    config=mesh_cfg), coll_pass)
        mesh_ok = not error_count(mesh_findings)
        if unattributed:
            mesh_detail += f" UNATTRIBUTED={unattributed}"
        report["programs"][f"mesh[{B_m}x{S_m}]"] = entry
        report["mesh_batch_axis_silent"] = batch_coll == 0
        report["mesh_ok"] = mesh_ok

    ok = batch_ok and mesh_ok
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0 if ok else 3
    print(f"halo audit: model={args.model} P={args.nparts} "
          f"atoms={report['n_atoms']} e_split={graph.e_split}/{graph.e_cap}")
    for name, entry in report["programs"].items():
        parts = " ".join(f"{k}={v}" for k, v in entry.items()
                         if k not in ("total", "ppermutes_by_scope",
                                      "by_axis"))
        print(f"  {name:<28} total={entry['total']:<4} {parts}")
        for ax, cnt in entry.get("by_axis", {}).items():
            print(f"      axis {ax}: "
                  + " ".join(f"{k}={v}" for k, v in cnt.items()))
        for scope, n in entry.get("ppermutes_by_scope", {}).items():
            print(f"      {n:3d}x {scope}")
    if args.batch > 0:
        verdict = "independent of B" if batch_ok else "DEPEND ON B (bug!)"
        print(f"  batched collective counts: {verdict}")
    if mesh_bs is not None:
        verdict = ("batch axis silent, spatial matches the ring"
                   if mesh_ok else "VIOLATED (bug!)")
        print(f"  mesh placement {mesh_bs[0]}x{mesh_bs[1]}: {verdict} "
              f"[{mesh_detail}]")
    return 0 if (batch_ok and mesh_ok) else 3


if __name__ == "__main__":
    raise SystemExit(main())
