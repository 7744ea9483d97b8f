"""Radial basis functions and cutoff envelopes (pure JAX, jit/grad-safe).

The bases used across the model zoo:
  - GaussianExpansion     (CHGNet-style smeared distances)
  - SphericalBesselBasis  (matgl TensorNet / MACE-style j0 Bessel basis)
  - FourierExpansion      (CHGNet angle features)
  - polynomial_cutoff     (MACE/CHGNet smooth envelope)
  - cosine_cutoff         (Behler-style envelope)
  - xplor_cutoff          (NequIP/SevenNet switching envelope)

All functions are smooth at the cutoff so forces stay continuous.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def spherical_bessel_basis(d, cutoff: float, num_basis: int):
    """Normalized j0 Bessel basis: sqrt(2/rc) * sin(n pi d / rc) / d.

    Safe at d=0 (returns the n*pi/rc limit).
    """
    n = jnp.arange(1, num_basis + 1, dtype=d.dtype)
    rc = jnp.asarray(cutoff, dtype=d.dtype)
    x = d[..., None]
    arg = n * jnp.pi * x / rc
    small = x < 1e-8
    safe_x = jnp.where(small, 1.0, x)
    out = jnp.sqrt(2.0 / rc) * jnp.sin(arg) / safe_x
    limit = jnp.sqrt(2.0 / rc) * n * jnp.pi / rc
    return jnp.where(small, limit, out)


def radial_bessel(d, frequencies, cutoff: float):
    """matgl ``RadialBesselFunction``: sqrt(2/rc) * sin(freq * d/rc) / d.

    ``frequencies`` is a learnable (R,) vector (init n*pi — at which the basis
    vanishes smoothly at the cutoff). Safe at d=0 (returns the freq/rc limit).
    Used by the matgl-parity CHGNet/TensorNet paths; the fixed-frequency
    variant above stays for MACE.
    """
    rc = jnp.asarray(cutoff, dtype=d.dtype)
    f = frequencies.astype(d.dtype)
    x = d[..., None]
    small = x < 1e-8
    safe_x = jnp.where(small, 1.0, x)
    out = jnp.sqrt(2.0 / rc) * jnp.sin(f * safe_x / rc) / safe_x
    limit = jnp.sqrt(2.0 / rc) * f / rc
    return jnp.where(small, limit, out)


def matgl_fourier_expansion(x, frequencies, interval: float = np.pi):
    """matgl ``FourierExpansion``: interleaved [cos(0x), sin(1x), cos(1x),
    sin(2x), cos(2x), ...] / interval, with learnable frequencies 0..max_f.

    x: (...,) -> (..., 2*max_f + 1). CHGNet's angle basis over x = theta.
    The layout and 1/interval scaling match matgl exactly so converted
    ``angle_embedding`` weights see the features they were trained on.
    """
    f = frequencies.astype(x.dtype)
    arg = x[..., None] * f * (np.pi / interval)
    cos = jnp.cos(arg)                   # (..., max_f + 1)
    sin = jnp.sin(arg[..., 1:])          # (..., max_f)
    out = jnp.zeros(x.shape + (2 * (f.shape[0] - 1) + 1,), dtype=x.dtype)
    out = out.at[..., 0::2].set(cos)
    out = out.at[..., 1::2].set(sin)
    return out / interval


def matgl_polynomial_cutoff(r, cutoff: float, p: int = 5):
    """matgl ``polynomial_cutoff``: the same envelope polynomial but with
    matgl's exact boundary semantics — evaluated on the raw ratio (no lower
    clamp) and hard-zeroed above the cutoff. matgl's CHGNet applies this
    *elementwise to the bessel expansion values*, not to distances (the
    reference wrapper replicates that call, reference
    implementations/matgl/models/chgnet.py:119-124, 174-182), so parity
    requires the unclamped form: expansion values can be negative.
    """
    x = r / cutoff
    p = int(p)
    c1 = -(p + 1.0) * (p + 2.0) / 2.0
    c2 = p * (p + 2.0)
    c3 = -p * (p + 1.0) / 2.0
    poly = 1.0 + c1 * x**p + c2 * x ** (p + 1) + c3 * x ** (p + 2)
    return jnp.where(r <= cutoff, poly, 0.0)


def polynomial_cutoff(d, cutoff: float, p: int = 6):
    """MACE-style polynomial envelope: 1 at 0, C^2-smooth 0 at cutoff."""
    x = d / cutoff
    x = jnp.clip(x, 0.0, 1.0)
    c1 = -(p + 1.0) * (p + 2.0) / 2.0
    c2 = p * (p + 2.0)
    c3 = -p * (p + 1.0) / 2.0
    return 1.0 + c1 * x**p + c2 * x ** (p + 1) + c3 * x ** (p + 2)


def cosine_cutoff(d, cutoff: float):
    """0.5 (cos(pi d / rc) + 1), zero beyond the cutoff."""
    return jnp.where(d < cutoff, 0.5 * (jnp.cos(jnp.pi * d / cutoff) + 1.0), 0.0)


def xplor_cutoff(d, cutoff: float, cutoff_on: float):
    """XPLOR switching envelope: 1 below ``cutoff_on``, then
    (rc^2 - d^2)^2 (rc^2 + 2 d^2 - 3 r_on^2) / (rc^2 - r_on^2)^3 down to 0
    at ``cutoff`` with zero slope at both ends, 0 beyond."""
    rc2, on2 = cutoff * cutoff, cutoff_on * cutoff_on
    d2 = d * d
    switch = ((rc2 - d2) ** 2 * (rc2 + 2.0 * d2 - 3.0 * on2)
              / (rc2 - on2) ** 3)
    return jnp.where(d < cutoff_on, 1.0, jnp.where(d < cutoff, switch, 0.0))
