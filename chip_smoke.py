"""chip_smoke.py — does the system still start on the chip?

    python3 chip_smoke.py        # one process, no arguments, no switches

Drives the main path once through the entry points a user calls, at the
full width of MACE-MP-0 medium (128 channels, l_max = a_lmax = 3,
correlation 3, two interactions, 95 species; random weights from a seed):

- **MD-1**   ``DistPotential(num_partitions=1, bfloat16)`` under
  ``MolecularDynamics`` on a 3,072-atom perturbed cell, checked against a
  float32 / XLA / ``precision=highest`` oracle computed on the same chip
  (and the oracle against itself on the default kernels);
- **MD-4**   the same structure over four chips (only where jax reports
  at least four devices), checked against MD-1;
- **SERVE**  a ``ServeEngine`` burst: eight same-size requests and one
  poisoned one, each answer checked against the single-structure path;
- **KERNELS** every Pallas kernel compiled for the chip at a
  published-width shape and compared with the XLA path; the table must
  agree with ``kernels/dispatch.TPU_DEFAULT_MODE``.

Any failed check raises: there is no handler that records an error and
carries on. The script exits non-zero unless ``jax.devices()[0].platform
== "tpu"``. The last two lines of stdout are JSON objects. The first is
the summary: versions, per-phase ``compile_s`` / ``step_ms`` (smoke timings
of single runs, not benchmark results), the kernel table, the measured
parity deltas, and ``"claim": null``. The LAST line is the verdict and
holds exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` as
jax reports the device; it is printed only after every phase passed.

The phases are plain functions of a model, a structure and a band, so
``tests/test_chip_smoke.py`` runs them at toy width on the virtual CPU
mesh; only :func:`main` insists on a TPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
import time
from importlib import metadata

import numpy as np

# Parity bands asserted on the chip. Each entry is (band, measured): the
# band is what the check allows, `measured` is what PR 21 read on a TPU v5
# lite with the sizes in main() (chip run of this script, CHANGES.md PR 21).
# They hold for that device and those sizes. CPU tolerances do not carry
# over: on the chip float32 matmuls are bf16 passes unless a precision says
# otherwise (which is why coordinates carry geometry.COORD_PRECISION).
BANDS = {
    # MD-1 bfloat16 / default kernels vs float32 / XLA / highest
    "bf16_vs_f32": {"dE_per_atom": (3e-2, 1.07e-2), "dF_rel": (4e-2, 1.20e-2),
                    "dS_rel": (4e-2, 1.17e-2)},
    # float32 / default kernels vs float32 / XLA, both at highest
    "kernels_vs_xla": {"dE_per_atom": (1e-6, 0.0), "dF_rel": (1e-5, 1.7e-6),
                       "dS_rel": (1e-5, 1.7e-7)},
    # |sum_i F_i| per component: translation invariance, eV/A
    "net_force": (1e-3, 2.2e-5),
    # MD-4 (four chips) vs MD-1 (one chip), both bfloat16
    "p4_vs_p1": {"dE_per_atom": (5e-4, 5.4e-5), "dF_rel": (3e-2, 7.5e-3),
                 "dS_rel": (1e-3, 6.0e-5)},
    # ServeEngine batch vs DistPotential(num_partitions=1), both bfloat16
    "serve_vs_single": {"dE_per_atom": (1e-5, 5.3e-8), "dF_rel": (1e-3, 0.0),
                        "dS_rel": (1e-4, 5.9e-7)},
    # Pallas kernel vs XLA float32/highest, max |d| / max |ref|
    "kernel_float32": (1e-5, 2.3e-7),
    "kernel_bfloat16": (1e-2, 3.2e-3),
}

UNIT_FCC = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileWatch:
    """Counts the XLA executables this process builds, and the seconds it
    spends tracing, lowering and compiling them, from jax's own monitoring
    events. A persistent-cache hit still counts as an executable (the
    program was new to this process) but costs almost no seconds."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.executables = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self._EVENTS:
            self.seconds += seconds
            self.executables += event == self._EVENTS[2]

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    @contextlib.contextmanager
    def window(self):
        """Yields a dict that, on exit, holds what happened inside the
        block: ``executables`` built, ``compile_s`` spent on them,
        persistent ``cache_hits`` and the block's ``wall_s``."""
        out = {}
        before = (self.executables, self.seconds, self.cache_hits)
        t0 = time.perf_counter()
        yield out
        out.update(executables=self.executables - before[0],
                   compile_s=round(self.seconds - before[1], 2),
                   cache_hits=self.cache_hits - before[2],
                   wall_s=round(time.perf_counter() - t0, 2))


def build_cell(reps, seed: int, a: float = 3.9, sigma: float = 0.04):
    """Perturbed fcc 'Si-like' supercell, the benchmark's recipe
    (benchmark/harness/structures.py): 4 * prod(reps) atoms."""
    from distmlip_tpu import geometry
    from distmlip_tpu.calculators import Atoms

    rng = np.random.default_rng(seed)
    frac, lattice = geometry.make_supercell(UNIT_FCC, np.eye(3) * a, reps)
    cart = geometry.frac_to_cart(frac, lattice) + rng.normal(
        0, sigma, (len(frac), 3))
    return Atoms(numbers=np.full(len(cart), 14), positions=cart,
                 cell=lattice)


def assert_slab_rule(atoms, cutoff: float, skin: float, parts: int) -> None:
    extent = float(np.linalg.norm(atoms.cell[0]))
    if not extent / parts > 2.0 * (cutoff + skin):
        raise AssertionError(
            f"x extent {extent:.1f} A / {parts} does not exceed "
            f"2 * (cutoff + skin) = {2 * (cutoff + skin):.1f} A")


def parity(result: dict, ref: dict, n_atoms: int) -> dict:
    """Deltas between two result dicts of one structure: energy per atom,
    forces and stress as Frobenius-relative errors."""
    def rel(a, b):
        return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                     / max(np.linalg.norm(np.asarray(b)), 1e-30))

    return {"dE_per_atom": abs(result["energy"] - ref["energy"]) / n_atoms,
            "dF_rel": rel(result["forces"], ref["forces"]),
            "dS_rel": rel(result["stress"], ref["stress"])}


def assert_within(name: str, deltas: dict, band: dict) -> None:
    for key, (limit, _measured) in band.items():
        if not deltas[key] <= limit:
            raise AssertionError(
                f"{name}: {key} = {deltas[key]:.3e} outside band {limit:.1e}")


def with_dtype(model, dtype: str):
    """The same model class and config at another compute dtype."""
    return type(model)(dataclasses.replace(model.cfg, dtype=dtype))


def assert_finite(name: str, result: dict) -> None:
    for key in ("energy", "forces", "stress"):
        if not np.all(np.isfinite(result[key])):
            raise AssertionError(f"{name}: non-finite {key}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_md1(model, params, atoms, watch: CompileWatch, *, bands: dict,
              steps: int = 4, skin: float = 0.5, kernels=None) -> dict:
    """bfloat16 MD on one device vs the float32/XLA/highest oracle."""
    import jax

    from distmlip_tpu.calculators import DistPotential, MolecularDynamics

    if steps < 2:
        raise ValueError("need >= 2 steps to see a step after the first")
    pot = DistPotential(model, params, num_partitions=1, skin=skin,
                        compute_dtype="bfloat16", kernels=kernels)
    atoms.set_maxwell_boltzmann_velocities(
        300.0, rng=np.random.default_rng(1))
    with watch.window() as first:
        md = MolecularDynamics(atoms, pot, ensemble="nve", timestep=1.0)

    def timed_step(i):
        t0 = time.perf_counter()
        # calculate() hands back host arrays (np.asarray of the device
        # result), so the device work of the step has ended when step()
        # returns: the host clock closes on a finished step
        md.step()
        ms = round(1e3 * (time.perf_counter() - t0), 1)
        assert_finite(f"MD-1 step {i}", md.results)
        return ms

    step_ms = [timed_step(0)]
    rebuilds = pot.rebuild_count
    with watch.window() as steady:
        step_ms += [timed_step(i) for i in range(1, steps)]
    rebuilds = pot.rebuild_count - rebuilds
    if steady["executables"] or rebuilds:
        raise AssertionError(
            f"MD-1: {steady['executables']} executables built and {rebuilds} "
            f"graphs rebuilt after step 1, with the skin still holding")
    result = md.results
    net = float(np.abs(result["forces"].sum(axis=0)).max())
    if not net <= bands["net_force"][0]:
        raise AssertionError(f"MD-1: |sum F| = {net:.3e} eV/A")

    # the oracle, and beside it the same float32/highest program on the
    # default kernels: bf16 noise (percents) would hide a wrong kernel,
    # float32 against float32 does not
    model32 = with_dtype(model, "float32")
    with watch.window() as oracle, jax.default_matmul_precision("highest"):
        ref = DistPotential(model32, params, num_partitions=1,
                            kernels=False).calculate(atoms)
        same = DistPotential(model32, params, num_partitions=1,
                             kernels=kernels).calculate(atoms)
    assert_finite("MD-1 oracle", ref)
    deltas = parity(result, ref, len(atoms))
    assert_within("MD-1 bf16 vs f32/highest", deltas, bands["bf16_vs_f32"])
    kernel_deltas = parity(same, ref, len(atoms))
    assert_within("MD-1 f32 default kernels vs f32 XLA", kernel_deltas,
                  bands["kernels_vs_xla"])
    return {
        "n_atoms": len(atoms), "compile_s": first["compile_s"],
        "cache_hits": first["cache_hits"], "first_call_s": first["wall_s"],
        "step_ms": step_ms,
        "executables_after_step_1": steady["executables"],
        "rebuilds_after_step_1": rebuilds,
        "kernel_ops": pot.last_stats["kernel_ops"],
        "net_force": net, "oracle_compile_s": oracle["compile_s"],
        "bf16_vs_f32": deltas, "kernels_vs_xla": kernel_deltas,
        "result": result,
    }


def phase_md4(model, params, atoms, reference: dict, watch: CompileWatch, *,
              bands: dict, skin: float = 0.5, kernels=None) -> dict:
    """The MD-1 structure over four devices, checked against MD-1."""
    from distmlip_tpu.calculators import DistPotential

    assert_slab_rule(atoms, float(model.cfg.cutoff), skin, 4)
    pot = DistPotential(model, params, num_partitions=4, skin=skin,
                        compute_dtype="bfloat16", kernels=kernels)
    with watch.window() as first:
        result = pot.calculate(atoms)
    assert_finite("MD-4", result)
    t0 = time.perf_counter()
    pot.calculate(atoms)
    step_ms = round(1e3 * (time.perf_counter() - t0), 1)

    graph = pot._cache[0]
    devices = graph.edge_src.sharding.device_set
    if len(devices) != 4 or len(graph.edge_src.addressable_shards) != 4:
        raise AssertionError(f"MD-4: graph lives on {len(devices)} devices")
    in_use = None
    if next(iter(devices)).platform == "tpu":
        in_use = {str(d.id): d.memory_stats()["bytes_in_use"]
                  for d in devices}
        if not all(v > 0 for v in in_use.values()):
            raise AssertionError(f"MD-4: idle device, bytes_in_use {in_use}")
    hlo = pot._potential.lower(pot.params, graph,
                               graph.positions).compile().as_text()
    permutes = {k: hlo.count(k) for k in (
        "collective-permute-start(", "collective-permute-done(",
        "collective-permute(")}
    if not sum(permutes.values()) > 0:
        raise AssertionError("MD-4: no collective-permute in the compiled "
                             "program")
    deltas = parity(result, reference, len(atoms))
    assert_within("MD-4 vs MD-1", deltas, bands["p4_vs_p1"])
    return {
        "devices": sorted(str(d) for d in devices),
        "compile_s": first["compile_s"], "cache_hits": first["cache_hits"],
        "first_call_s": first["wall_s"], "step_ms": step_ms,
        "bytes_in_use": in_use, "collective_permutes": permutes,
        "kernel_ops": pot.last_stats["kernel_ops"], "p4_vs_p1": deltas,
    }


def phase_serve(model, params, structures, watch: CompileWatch, *,
                bands: dict, kernels=None, timeout_s: float = 900.0) -> dict:
    """A ServeEngine burst of same-size requests plus one poisoned one;
    every answer is checked against the single-structure path."""
    from distmlip_tpu.calculators import BatchedPotential, DistPotential
    from distmlip_tpu.serve import ServeEngine

    model = with_dtype(model, "bfloat16")
    poison = structures[0].copy()
    poison.positions[0, 0] = np.nan
    # start=False stages the whole burst before the scheduler wakes, so the
    # good requests leave as ONE batch: one bucket, one compile
    engine = ServeEngine(
        BatchedPotential(model, params, kernels=kernels),
        max_batch=len(structures), start=False)
    futures = [engine.submit(a) for a in structures]
    poisoned = engine.submit(poison)
    with watch.window() as burst:
        engine.start()
        results = [f.result(timeout=timeout_s) for f in futures]
    error = poisoned.exception(timeout=timeout_s)
    if error is None:
        raise AssertionError("SERVE: the poisoned request was answered")
    if not engine.drain(timeout=timeout_s):
        raise AssertionError("SERVE: drain() timed out")
    engine.close(timeout=timeout_s)
    if engine.compile_count != 1:
        raise AssertionError(
            f"SERVE: {engine.compile_count} compiles for one bucket")

    single = DistPotential(model, params, num_partitions=1, kernels=kernels)
    worst = {}
    for atoms, result in zip(structures, results):
        assert_finite("SERVE", result)
        deltas = parity(result, single.calculate(atoms), len(atoms))
        worst = {k: max(v, worst.get(k, 0.0)) for k, v in deltas.items()}
    assert_within("SERVE vs single", worst, bands["serve_vs_single"])
    return {
        "requests": len(structures), "atoms_each": len(structures[0]),
        "compile_s": burst["compile_s"], "cache_hits": burst["cache_hits"],
        "burst_s": burst["wall_s"], "compiles": engine.compile_count,
        "poisoned_failed_with": type(error).__name__,
        "completed": engine.stats.completed, "failed": engine.stats.failed,
        "kernel_ops": engine.potential.last_stats["kernel_ops"],
        "serve_vs_single": worst,
    }


def _kernel_cases(seed: int):
    """One published-width call per Pallas kernel: ``(op, build)`` where
    ``build(dtype)`` returns ``(kernel_fn, xla_fn, args)``; the raw kernel
    entry points compile for the backend jax runs on."""
    import jax
    import jax.numpy as jnp

    from distmlip_tpu.kernels.dispatch import repeat_edge_block
    from distmlip_tpu.kernels.segment import (pallas_edge_aggregate,
                                              pallas_segment_sum,
                                              pallas_segment_sum_into)
    from distmlip_tpu.kernels.so3 import (packed_m_layout,
                                          s2_activation_pallas, s2_tables,
                                          so2_conv_pallas,
                                          so2_conv_reference, wigner_cols,
                                          wigner_dcols_pallas,
                                          wigner_rotate_pallas,
                                          wigner_rotate_reference)
    from distmlip_tpu.models import (ESCN, ESCNConfig, EquiformerV2,
                                     EquiformerV2Config)
    from distmlip_tpu.ops.nn import gated_mlp
    from distmlip_tpu.ops.segment import masked_segment_sum
    from distmlip_tpu.ops.so3_e3nn import (CoeffLayout,
                                           wigner_blocks_from_edges)

    rng = np.random.default_rng(seed)

    def sorted_ids(e, n):
        pad = e // 64
        ids = np.sort(rng.integers(0, n, e - pad)).astype(np.int32)
        return (jnp.asarray(np.concatenate([ids, np.full(pad, ids[-1])])),
                jnp.asarray(np.arange(e) < e - pad))

    def normal(shape, dtype, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype=dtype)

    def segment_sum(dtype):
        # MACE's second-interaction scan chunk: M is (E_c, nQ, 128) with
        # E_c = edge_chunk = 32768 and nQ = 40 at l_max = a_lmax = 3
        e, n = 32768, 3072
        ids, mask = sorted_ids(e, n)
        data = normal((e, 40, 128), dtype)
        return (lambda d: pallas_segment_sum(d, ids, n, mask),
                lambda d: masked_segment_sum(d, ids, n, mask,
                                             indices_are_sorted=True),
                (data,))

    def edge_aggregate(dtype):
        # CHGNet's atom-conv message at units = 64: gated MLP on
        # [v_src | v_dst | e] (192 -> 64 -> 64), times the bond weights
        e, n, c = 131072, 3072, 64
        ids, mask = sorted_ids(e, n)
        src = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
        v, edge, abw = (normal((n, c), dtype), normal((e, c), dtype),
                        normal((e, c), dtype))
        dims = [(3 * c, c), (c,), (c, c), (c,)] * 2
        consts = [normal(s, dtype, 1.0 / np.sqrt(s[0])) for s in dims]

        def message(vs, vd, e_sl, w_sl, *ws):
            layers = [{"w": ws[i], "b": ws[i + 1]} for i in range(0, 8, 2)]
            return gated_mlp({"core": layers[:2], "gate": layers[2:]},
                             jnp.concatenate([vs, vd, e_sl], axis=-1)) * w_sl

        def kernel(v_, edge_, abw_, *ws):
            return pallas_edge_aggregate(
                message, [("gather", v_, src), ("gather", v_, ids), edge_,
                          abw_], ids, n, mask, out_shape=(c,),
                out_dtype=dtype, consts=ws)

        def xla(v_, edge_, abw_, *ws):
            return masked_segment_sum(
                message(v_[src], v_[ids], edge_, abw_, *ws), ids, n, mask,
                indices_are_sorted=True)

        return kernel, xla, (v, edge, abw, *consts)

    def so2_conv(dtype):
        # eSCN/UMA-S SO(2) block: 128 channels, l_max = 2, one scan chunk
        e, c = 32768, 128
        _, _, segments = packed_m_layout(
            ESCN(ESCNConfig(channels=c, l_max=2)).m_idx)
        s = sum(nl * (1 if m == 0 else 2) for m, _, nl in segments)
        weights = []
        for m, _, nl in segments:
            for _ in range(1 if m == 0 else 2):
                weights.append(normal((nl * c, nl * c), dtype,
                                      1.0 / np.sqrt(nl * c)))
        h = normal((e, s, c), dtype)
        return (lambda h_, *ws: so2_conv_pallas(h_, list(ws), segments, c),
                lambda h_, *ws: so2_conv_reference(h_, list(ws), segments, c),
                (h, *weights))

    def wigner_rotate(dtype):
        # UMA-S's rotations on one scan chunk: sender and receiver rows
        # into the edge frame, the SO(2) output back, and the cotangent of
        # the 35 block columns (scaled to the rows' size), side by side
        e, c, lay = 32768, 128, CoeffLayout(2, 2)
        ms = tuple(lay.signed_ms)
        kw = dict(l_max=2, m_max=2, ms=ms, channels=c)
        rhat = rng.normal(size=(e, 3))
        rhat /= np.linalg.norm(rhat, axis=1, keepdims=True)
        cols = wigner_cols(wigner_blocks_from_edges(
            2, jnp.asarray(rhat, jnp.float32)))
        labs = [normal((e, 9 * c), dtype) for _ in range(2)]
        pieces = [normal((e, lay.m_size(abs(m)) * c), dtype) for m in ms]

        def side_by_side(fr, out, dcols):
            return jnp.concatenate(
                [*fr, *out, (dcols / (2 * c)).astype(out[0].dtype)], axis=1)

        def kernel(cols_, xs, xd, *ys):
            fr = wigner_rotate_pallas(cols_, [xs, xd], n_ops=2, to_edge=True,
                                      **kw)
            return side_by_side(
                fr, wigner_rotate_pallas(cols_, ys, n_ops=1, to_edge=False,
                                         **kw),
                wigner_dcols_pallas([xs, xd], fr, **kw))

        def xla(cols_, xs, xd, *ys):
            to_edge = lambda c_: wigner_rotate_reference(
                c_, [xs, xd], n_ops=2, to_edge=True, **kw)
            fr, vjp = jax.vjp(to_edge, cols_)
            return side_by_side(
                fr, wigner_rotate_reference(cols_, ys, n_ops=1,
                                            to_edge=False, **kw),
                vjp(fr)[0])

        return kernel, xla, (cols, *labs, *pieces)

    def segment_sum_into(dtype):
        # one scan chunk added into the carried accumulator, both shapes
        # side by side: MACE's (E_c, 40, 128) messages into the flat
        # (n_cap, 5120) carry of mace-md-1c, UMA-S's (E_c, 1152) rows into
        # uma-md-1c's. A chunk of 78 built edges an atom lands in about 420
        # rows: here rows 5000 to 5419, across four dst tiles
        e = 32768
        ids, mask = sorted_ids(e, 420)
        ids = ids + 5000
        shapes = ((29568, (40, 128)), (9856, (1152,)))
        accs = [normal((n, int(np.prod(tr))), dtype) for n, tr in shapes]
        rows = [normal((e,) + tr, dtype) for _, tr in shapes]

        def both(add_into):
            return lambda a0, a1, d0, d1: jnp.concatenate(
                [add_into(a, d).reshape(-1) for a, d in ((a0, d0), (a1, d1))])

        return (both(lambda a, d: pallas_segment_sum_into(a, d, ids, mask)),
                both(lambda a, d: a + masked_segment_sum(
                    d, ids, a.shape[0], mask,
                    indices_are_sorted=True).reshape(a.shape)),
                (*accs, *rows))

    def segment_repeat(dtype):
        # the transpose of DimeNet++'s repeat over a slab of dimenet-pp-md-
        # 1c's in-line scan: 394,368 bond rows 128 lanes wide (the 124-wide
        # float32 source row on whole lane tiles) summed onto the two rows
        # of their 8,448 centre atoms (46 to 49 bonds a centre, one in 47
        # on the second row), in the dispatcher's edge blocks
        e, n = 394368, 2 * 8448
        deg = rng.integers(44, 50, 8192)
        ids = np.repeat(np.arange(8192), deg)[:e]
        ids = 2 * np.sort(np.concatenate([np.zeros(e - len(ids), np.int64),
                                          ids])) + (rng.random(e) < 1 / 47)
        ids = jnp.asarray(ids.astype(np.int32))
        return (lambda d: pallas_segment_sum(d, ids, n,
                                             edge_blk=repeat_edge_block(e)),
                lambda d: masked_segment_sum(d, ids, n),
                (normal((e, 128), dtype),))

    def s2_activation(dtype):
        # EquiformerV2's edge S2 activation on one scan chunk of
        # eqv2-31m-md-1c: the 19 coefficients of lmax 4 / mmax 2 for 64
        # channels and 16,384 edges, as the kernel takes them; the forward
        # and the backward side by side
        to_grid, from_grid = EquiformerV2(EquiformerV2Config()).edge_grid
        tables = s2_tables(to_grid, from_grid, dtype)
        x, dy = (normal((to_grid.shape[1], 64, 16384), dtype, 0.5)
                 for _ in range(2))

        def kernel(x_, dy_):
            return jnp.concatenate([
                s2_activation_pallas(x_, tables),
                s2_activation_pallas(dy_, tables, backward_of=x_)])

        def xla(x_, dy_):
            g, f = (jnp.asarray(t, x_.dtype) for t in (to_grid, from_grid))
            y, vjp = jax.vjp(lambda c: jnp.einsum(
                "kg,ghe->khe", f, jax.nn.silu(jnp.einsum(
                    "gk,khe->ghe", g, c))), x_)
            return jnp.concatenate([y, vjp(dy_)[0]])

        return kernel, xla, (x, dy)

    return (("segment_sum", segment_sum), ("edge_aggregate", edge_aggregate),
            ("so2_conv", so2_conv), ("wigner_rotate", wigner_rotate),
            ("segment_sum_into", segment_sum_into),
            ("segment_repeat", segment_repeat),
            ("s2_activation", s2_activation))


def phase_kernels(bands: dict, seed: int = 0) -> list:
    """Compile each Pallas kernel for this backend and compare it with the
    XLA path run in float32 under ``precision=highest``. Returns the table
    rows; raises if a row disagrees with ``TPU_DEFAULT_MODE``."""
    import jax
    import jax.numpy as jnp

    from distmlip_tpu.kernels.dispatch import TPU_DEFAULT_MODE

    rows = []
    for op, build in _kernel_cases(seed):
        default = TPU_DEFAULT_MODE[op]
        row = {"op": op, "default": default, "compiled": True,
               "max_rel_err": {}, "message": ""}
        for dtype in (jnp.float32, jnp.bfloat16):
            name = jnp.dtype(dtype).name
            kernel, xla, args = build(dtype)
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(jax.jit(xla)(
                    *[a.astype(jnp.float32) for a in args]))
            run = jax.jit(kernel)
            if default == "pallas":
                out = run(*args)
            else:
                # an op the table keeps on XLA is allowed to fail here:
                # the compiler's message is what the table quotes. Mosaic
                # refuses at lowering (NotImplementedError, ValueError,
                # LoweringException) or at compile (JaxRuntimeError), so
                # no narrower class covers it.
                try:
                    out = run(*args)
                except Exception as e:  # noqa: BLE001
                    row["compiled"] = False
                    row["message"] = f"{type(e).__name__}: {e}"[:600]
                    break
            out = np.asarray(jax.block_until_ready(out), dtype=np.float32)
            row["max_rel_err"][name] = float(
                np.abs(out - ref).max() / np.abs(ref).max())
        agrees = row["compiled"] and all(
            err <= bands[f"kernel_{name}"][0]
            for name, err in row["max_rel_err"].items())
        rows.append(row)
        log(f"KERNELS {op:<15} compiled={row['compiled']!s:<5} "
            f"max_rel_err={row['max_rel_err']} default={default} "
            f"{row['message'][:200]}")
        if agrees != (default == "pallas"):
            raise AssertionError(
                f"KERNELS: {op} compiled={row['compiled']} "
                f"err={row['max_rel_err']} but TPU_DEFAULT_MODE says "
                f"{default!r}: {row['message']}")
    return rows


# ---------------------------------------------------------------------------

def mace_mp0_medium():
    """MACE-MP-0 medium at full width (benchmark/configs/mace-mp0-medium.json)."""
    from distmlip_tpu.models import MACE, MACEConfig

    return MACE(MACEConfig(
        num_species=95, channels=128, l_max=3, a_lmax=3, hidden_lmax=1,
        correlation=3, num_interactions=2, num_bessel=8, radial_mlp=64,
        cutoff=5.0, avg_num_neighbors=14.0))


def verdict_line(device, count: int) -> str:
    """The last line of stdout, as the driver reads it: exactly ``ok`` and
    ``device`` = ``platform`` / ``kind`` / ``count``, no other key."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device.platform), "kind": str(device.device_kind),
        "count": int(count)}})


def main() -> int:
    if os.environ.get("DISTMLIP_KERNELS", "").strip().lower() == "interpret":
        print("chip_smoke: DISTMLIP_KERNELS=interpret would run the Pallas "
              "interpreter, not the chip's kernels; unset it",
              file=sys.stderr)
        return 2
    from distmlip_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    device = jax.devices()[0]
    identity = {"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices())}
    versions = {"jax": jax.__version__,
                "jaxlib": metadata.version("jaxlib"),
                "libtpu": metadata.version("libtpu")}
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax found platform "
              f"{device.platform!r} ({device.device_kind!r} x "
              f"{identity['count']})", file=sys.stderr)
        return 2
    from distmlip_tpu.utils.flops import device_peaks

    device_peaks(device)  # an unknown TPU kind is an error, here too
    log(f"device {identity} versions {versions} compile cache {cache_dir}")

    t_start = time.perf_counter()
    watch = CompileWatch()
    model = mace_mp0_medium()
    params = model.init(jax.random.PRNGKey(0))
    # 12 x 8 x 8 cells of 3.9 A: 3,072 atoms, and 46.8 A / 4 slabs clears
    # 2 * (cutoff + skin) = 11 A, so MD-4 partitions the same structure
    atoms = build_cell((12, 8, 8), seed=0)
    assert_slab_rule(atoms, float(model.cfg.cutoff), 0.5, 4)
    phases = {}

    md1 = phase_md1(model, params, atoms, watch, bands=BANDS)
    reference = md1.pop("result")
    phases["MD-1"] = md1
    log(f"MD-1 {md1}")
    # where MD-1's set-up went, as the benchmark's set-up metrics split it
    from distmlip_tpu.telemetry.trace import jax_cache_counts, phase_totals

    log(f"phases {json.dumps({**phase_totals(), **jax_cache_counts()})}")

    if identity["count"] >= 4:
        phases["MD-4"] = phase_md4(model, params, atoms, reference, watch,
                                   bands=BANDS)
        log(f"MD-4 {phases['MD-4']}")
    else:
        log(f"MD-4: not run ({identity['count']} device)")

    burst = [build_cell((3, 3, 3), seed=10 + i) for i in range(8)]
    phases["SERVE"] = phase_serve(model, params, burst, watch, bands=BANDS)
    log(f"SERVE {phases['SERVE']}")

    kernels = phase_kernels(BANDS)
    print(f"{'op':<16}{'compiled':<10}{'max rel err':<40}default")
    for row in kernels:
        errs = "  ".join(f"{k} {v:.1e}" for k, v in row["max_rel_err"].items())
        print(f"{row['op']:<16}{row['compiled']!s:<10}{errs or '-':<40}"
              f"{row['default']}")

    print(json.dumps({
        "summary": "chip_smoke", "device": identity, "versions": versions,
        "timings_are": "smoke timings of single runs, not benchmark results",
        "wall_s": round(time.perf_counter() - t_start, 1),
        "phases": phases, "kernels": kernels, "claim": None,
    }))
    # last line, and only when all of the above ran to the end (any failed
    # check raised before here)
    print(verdict_line(device, identity["count"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
