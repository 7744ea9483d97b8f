#!/usr/bin/env python
"""Microbenchmark: fused Pallas edge-aggregate vs the unfused XLA pipeline.

    python tools/kernel_bench.py [--sizes 100000,400000] [--widths 64,128]
        [--nodes N] [--iters 30] [--interpret] [--json]

The workload is the canonical message-passing inner loop every model
ships: gather src rows from an (N, W) node array, apply a per-edge
silu-gated (W_in -> W_out) edge MLP, and accumulate onto dst rows of a
dst-sorted layout (repeat-last padding + validity mask — the repo's
padding contract). The unfused arm is the historical XLA program
(materialized (E, W_out) messages + ``masked_segment_sum`` with the
sorted hint); the fused arm routes the SAME computation through
``kernels.fused_edge_aggregate``. Per (E, width) point it reports wall
time per iteration, speedup, and MFU from the shared analytic FLOP
count (``utils/flops.edge_aggregate_flops``) — so the win is RECORDED
(bench.py folds this into BENCH_*.json), not asserted.

Each record carries ``in_kernel_gather``: whether the node array fit
the dispatcher's VMEM budget (``DISTMLIP_KERNELS_VMEM``) and was
gathered INSIDE the kernel, or was pre-gathered by XLA (large N) with
only the compute+scatter fused — the two are different pipelines and
the published number must say which one it measured. Shrink ``--nodes``
or raise the env budget to force the in-kernel variant at large E.

``--interpret`` runs the kernel in interpreter mode — the chip-free
plumbing smoke (run it under ``JAX_PLATFORMS=cpu``; the speedup number is
meaningless there, only the machinery is under test). Without it the
kernels compile for whatever backend jax finds, so it needs a TPU.

Exit codes: 0 ok, 2 usage error.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_case(rng, e, n, w_in, w_out, dtype):
    import numpy as np

    ids = np.sort(rng.integers(0, n, e)).astype(np.int32)
    pad = max(8, e // 64)
    ids = np.concatenate([ids, np.full(pad, ids[-1], np.int32)])
    mask = np.concatenate([np.ones(e, bool), np.zeros(pad, bool)])
    node = rng.normal(size=(n, w_in)).astype(dtype)
    gate = rng.normal(size=(e + pad, w_in)).astype(dtype)
    src = rng.integers(0, n, e + pad).astype(np.int32)
    w = (rng.normal(size=(w_in, w_out)) / np.sqrt(w_in)).astype(dtype)
    return ids, mask, node, gate, src, w


def run_case(e, n, w_in, w_out, iters=30, interpret=False, seed=0,
             dtype="float32"):
    """One (E, width) point: {fused_s, unfused_s, speedup, mfu_*}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distmlip_tpu.kernels import Gather, fused_edge_aggregate
    from distmlip_tpu.ops.segment import masked_segment_sum
    from distmlip_tpu.utils.flops import edge_aggregate_flops, mfu

    from distmlip_tpu.kernels.dispatch import DEFAULT_VMEM_BUDGET

    rng = np.random.default_rng(seed)
    ids, mask, node, gate, src, w = build_case(rng, e, n, w_in, w_out,
                                               dtype)
    # over the dispatcher's VMEM budget, the node array is pre-gathered by
    # XLA and only the compute+scatter fuse — record WHICH variant ran so
    # the published number is attributable (a silent cap otherwise)
    in_kernel_gather = node.nbytes <= DEFAULT_VMEM_BUDGET
    ids, mask, node, gate, src, w = map(jnp.asarray,
                                        (ids, mask, node, gate, src, w))

    def edge_fn(rows, g_rows):
        return jax.nn.silu(rows * g_rows) @ w

    @jax.jit
    def unfused(node_, gate_):
        msg = edge_fn(jnp.take(node_, src, axis=0), gate_)
        return masked_segment_sum(msg, ids, n, mask,
                                  indices_are_sorted=True)

    mode = "interpret" if interpret else "pallas"

    @jax.jit
    def fused(node_, gate_):
        return fused_edge_aggregate(
            edge_fn, [Gather(node_, src), gate_], ids, n, mask,
            kernels=mode, diff_params=False)

    def timed(fn):
        out = fn(node, gate)  # compile + warm
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(node, gate)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters, out

    t_un, o_un = timed(unfused)
    t_fu, o_fu = timed(fused)
    err = float(jnp.max(jnp.abs(o_un - o_fu)))
    flops = edge_aggregate_flops(e, w_in, w_out)
    # None off-TPU: a CPU has no published peak to divide by
    mfu_un, mfu_fu = mfu(flops, t_un, 1), mfu(flops, t_fu, 1)
    return {
        "e": e, "nodes": n, "w_in": w_in, "w_out": w_out, "iters": iters,
        "mode": mode, "in_kernel_gather": in_kernel_gather,
        "unfused_s": round(t_un, 6), "fused_s": round(t_fu, 6),
        "speedup": round(t_un / t_fu, 3) if t_fu > 0 else 0.0,
        "flops": flops,
        "mfu_unfused": None if mfu_un is None else round(mfu_un, 5),
        "mfu_fused": None if mfu_fu is None else round(mfu_fu, 5),
        "max_abs_err": err,
    }


def run_sweep(sizes, widths, nodes=None, iters=30, interpret=False):
    """The bench.py entry: list of per-point records + a summary."""
    points = []
    for e in sizes:
        n = nodes or max(64, e // 16)
        for wd in widths:
            points.append(run_case(e, n, wd, wd, iters=iters,
                                   interpret=interpret))
    best = max((p["speedup"] for p in points), default=0.0)
    return {"points": points, "best_speedup": best,
            "mode": points[0]["mode"] if points else ""}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="kernel_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--sizes", default="100000,400000",
                    help="comma list of edge counts E")
    ap.add_argument("--widths", default="64,128",
                    help="comma list of feature widths (w_in = w_out)")
    ap.add_argument("--nodes", type=int, default=None,
                    help="node count N (default: E // 16)")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--interpret", action="store_true",
                    help="interpreter-mode kernels (chip-free smoke)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object instead of per-point lines")
    try:
        args = ap.parse_args(argv)
        sizes = [int(s) for s in args.sizes.split(",") if s.strip()]
        widths = [int(s) for s in args.widths.split(",") if s.strip()]
        if not sizes or not widths:
            raise ValueError("need at least one size and one width")
    except SystemExit as e:
        return 0 if e.code in (0, None) else 2
    except ValueError as e:
        print(f"usage error: {e}", file=sys.stderr)
        return 2

    out = run_sweep(sizes, widths, nodes=args.nodes, iters=args.iters,
                    interpret=args.interpret)
    if args.json:
        print(json.dumps(out, indent=2, sort_keys=True))
    else:
        for p in out["points"]:
            print(json.dumps(p, sort_keys=True))
        print(f"# best speedup {out['best_speedup']}x (mode={out['mode']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
