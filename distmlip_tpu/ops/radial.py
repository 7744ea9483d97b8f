"""Radial basis functions and cutoff envelopes (pure JAX, jit/grad-safe).

The bases used across the model zoo:
  - GaussianExpansion     (CHGNet-style smeared distances)
  - SphericalBesselBasis  (matgl TensorNet / MACE-style j0 Bessel basis)
  - FourierExpansion      (CHGNet angle features)
  - polynomial_cutoff     (MACE/CHGNet smooth envelope)
  - cosine_cutoff         (Behler-style envelope)
  - xplor_cutoff          (NequIP/SevenNet switching envelope)
  - dimenet_envelope, spherical_bessel_table, spherical_bessel_jl,
    legendre_rows         (DimeNet's radial and spherical bases)

All functions are smooth at the cutoff so forces stay continuous.
"""

from __future__ import annotations

import functools

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


# ---- DimeNet (arXiv:2003.03123, section 4; DimeNet++ arXiv:2011.14115) ----

def dimenet_envelope(x, exponent: int = 5):
    """DimeNet's envelope of ``x = d / c``: ``1/x + a x^(p-1) + b x^p +
    c x^(p+1)`` with ``p = exponent + 1`` (a = -(p+1)(p+2)/2, b = p(p+2),
    c = -p(p+1)/2), zero from ``x = 1`` on, where its value and slope
    vanish. ``x`` must be positive (the caller gives padded rows x = 1)."""
    p = exponent + 1
    a = -(p + 1) * (p + 2) / 2.0
    b = p * (p + 2.0)
    c = -p * (p + 1) / 2.0
    xp = x ** (p - 1)
    u = 1.0 / x + a * xp + b * xp * x + c * xp * x * x
    return jnp.where(x < 1.0, u, 0.0)


def _spherical_jn64(l: int, z):
    """j_l in float64 numpy by the upward recurrence (host tables only)."""
    j_prev, j = np.sin(z) / z, np.sin(z) / z ** 2 - np.cos(z) / z
    if l == 0:
        return j_prev
    for k in range(1, l):
        j_prev, j = j, (2 * k + 1) / z * j - j_prev
    return j


@functools.lru_cache(maxsize=None)
def spherical_bessel_table(num_spherical: int, num_radial: int):
    """Host float64 ``(roots, norms)``, each ``(num_spherical,
    num_radial)``: ``z_ln`` the n-th positive root of ``j_l`` and ``N_ln =
    sqrt(2) / |j_{l+1}(z_ln)|``, so that ``N_ln j_l(z_ln x)`` has unit
    norm on [0, 1] with weight x^2. The roots of ``j_l`` interlace those of
    ``j_{l-1}`` (the roots of j_0 are n pi): each is found by bisection
    between two neighbouring roots of the order below."""
    count = num_radial + num_spherical
    roots = [np.pi * np.arange(1, count + 1, dtype=np.float64)]
    for l in range(1, num_spherical):
        below = roots[-1]
        found = []
        for lo, hi in zip(below[:-1], below[1:]):
            f_lo = _spherical_jn64(l, lo)
            for _ in range(200):
                mid = 0.5 * (lo + hi)
                f_mid = _spherical_jn64(l, mid)
                if np.sign(f_mid) == np.sign(f_lo):
                    lo, f_lo = mid, f_mid
                else:
                    hi = mid
            found.append(0.5 * (lo + hi))
        roots.append(np.array(found))
    z = np.stack([r[:num_radial] for r in roots])
    norms = np.sqrt(2.0) / np.abs(np.stack(
        [_spherical_jn64(l + 1, z[l]) for l in range(num_spherical)]))
    z.flags.writeable = norms.flags.writeable = False  # shared by the cache
    return z, norms


def spherical_bessel_jl(num_spherical: int, z):
    """``[j_0(z), ..., j_{L-1}(z)]`` for ``z`` of shape ``(..., L, n)``
    (row l of the second last axis gives j_l): j_0 and j_1 in closed form,
    the rest by the upward recurrence ``j_{l+1} = (2l+1)/z j_l - j_{l-1}``.
    In float32 it stays within 1e-6 of the largest value of each row over
    arguments ``z_ln x``, x in [0.5, 1] (a bond at least half the cutoff
    long); below x = 0.2 the recurrence loses digits, which no bond of a
    solid reaches."""
    s, c = jnp.sin(z), jnp.cos(z)
    rows = [s / z, s / (z * z) - c / z]
    out = [rows[0][..., 0, :]]
    j_prev, j = rows
    for l in range(1, num_spherical):
        out.append(j[..., l, :])
        j_prev, j = j, (2 * l + 1) / z * j - j_prev
    return jnp.stack(out[:num_spherical], axis=-2)


def legendre_rows(cos, num_spherical: int):
    """``Y_l0 = sqrt((2l + 1) / 4 pi) P_l(cos)`` for l < ``num_spherical``,
    ``(..., L)``, by Bonnet's recurrence on ``cos`` itself: no arccos, whose
    slope diverges at the collinear triplets of a lattice."""
    p = [jnp.ones_like(cos), cos]
    for l in range(1, num_spherical - 1):
        p.append(((2 * l + 1) * cos * p[l] - l * p[l - 1]) / (l + 1))
    scale = np.sqrt((2 * np.arange(num_spherical) + 1) / (4 * np.pi))
    return jnp.stack(p[:num_spherical], axis=-1) * scale.astype(np.float32)
