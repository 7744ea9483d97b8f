"""The numbers that decide ``correct``, and their limits."""

from __future__ import annotations

import numpy as np

from .structures import AMU_A2_FS2_TO_EV


def relative(a, b) -> float:
    """Frobenius norm of ``a - b`` over that of ``b``."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def md_numbers(program: dict, reference: dict, rounding_forces,
               masses: np.ndarray, timestep_fs: float) -> dict:
    """What one velocity-Verlet step produced, against the reference's
    energy and forces at the positions the step moved to.

    - ``force_rel_err``: the program's forces against the reference's.
      On a lattice the net force is what is left of contributions that all
      but cancel, so this number swings with the weights: 0.005 to 0.04
      over a dozen seeds of one cell. Reported, not held to a limit.
    - ``force_err_vs_rounding``: the same error over the error of
      ``rounding_forces``, the reference computed in the precision the
      configuration is served in. Both scale alike with the weights, so
      the ratio is steady from seed to seed: about 1 for a program that
      rounds as its configuration states, an order above in the precision
      below.
    - ``energy_err_per_atom``: eV per atom (reported only; not a number
      where a sample of the atoms is compared).
    - ``kick_rel_err``: whether the step moved the state by those forces.
      A velocity-Verlet step ends with v = v_half + dt/2 a(new positions),
      and v_half = (new - old positions) / dt. So
      v - (new - old) / dt has to be dt/2 F_ref / m. A step that returns
      its state unchanged, or forces that never reach the integrator, read
      near 1 or far above; a sound step reads as ``force_rel_err`` does.
    """
    dt = float(timestep_fs)
    n = len(masses)
    v_half = (program["positions"] - program["prev_positions"]) / dt
    kick = program["velocities"] - v_half
    expected = 0.5 * dt * reference["forces"] / (
        masses[:, None] * AMU_A2_FS2_TO_EV)
    error = np.linalg.norm(program["forces"] - reference["forces"])
    rounding = np.linalg.norm(np.asarray(rounding_forces, np.float64)
                              - reference["forces"])
    return {
        "force_rel_err": relative(program["forces"], reference["forces"]),
        "force_err_vs_rounding": float(error / max(rounding, 1e-300)),
        "energy_err_per_atom": float("nan") if program["energy"] is None
        else abs(program["energy"] - reference["energy"]) / n,
        "kick_rel_err": relative(kick, expected),
    }


def against_limits(numbers: dict, limits: dict) -> list:
    """[{"name", "value", "limit"}, ...] for every number that has a
    limit; a number that is not finite counts as over it."""
    out = []
    for name, entry in limits.items():
        value = float(numbers[name])
        out.append({"name": name,
                    "value": value if np.isfinite(value) else float("inf"),
                    "limit": float(entry["limit"])})
    return out
