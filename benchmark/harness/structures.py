"""Structures made from the seed. A copy of the repo's perturbed fcc
recipe (``chip_smoke.build_cell``, ``tools/bench_common.build_bench_atoms``;
PERF.md, Open questions), in plain numpy so that the reference gets the
same atoms without the program."""

from __future__ import annotations

import numpy as np

UNIT_FCC = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
KB = 8.617333262e-5                 # eV/K
AMU_A2_FS2_TO_EV = 103.642696562    # 1 amu (A/fs)^2 in eV
MASSES = {14: 28.0855}              # amu


def perturbed_fcc(reps, a: float, sigma: float, number: int, seed: int):
    """4 * prod(reps) atoms of one species on an fcc lattice of constant
    ``a``, each displaced by N(0, sigma) per component. Returns
    ``(numbers, positions, cell)``; image-major order, as
    ``distmlip_tpu.geometry.make_supercell`` tiles."""
    rng = np.random.default_rng(seed)
    nx, ny, nz = (int(r) for r in reps)
    shifts = np.stack(np.meshgrid(np.arange(nx), np.arange(ny),
                                  np.arange(nz), indexing="ij"),
                      axis=-1).reshape(-1, 3)
    frac = (UNIT_FCC[None, :, :] + shifts[:, None, :]).reshape(-1, 3)
    positions = frac * a + rng.normal(0.0, sigma, frac.shape)
    cell = np.diag([nx * a, ny * a, nz * a]).astype(np.float64)
    return np.full(len(positions), int(number), np.int32), positions, cell


def maxwell_boltzmann(numbers, temperature_k: float, seed: int):
    """Velocities (A/fs) at ``temperature_k`` with zero total momentum."""
    rng = np.random.default_rng(seed)
    masses = np.array([MASSES[int(z)] for z in numbers])
    sigma = np.sqrt(KB * temperature_k / (masses * AMU_A2_FS2_TO_EV))
    v = rng.normal(size=(len(numbers), 3)) * sigma[:, None]
    return v - (masses[:, None] * v).sum(axis=0) / masses.sum()
