"""e3nn-convention real-SH rotations — the fairchem/UMA Wigner pipeline.

The UMA eSCN backbone (reference implementations/uma/escn_md.py:74-130)
builds per-edge Wigner matrices as ``X(alpha) J X(beta) J X(gamma)`` from
precomputed per-l ``Jd`` tables, in e3nn's real-spherical-harmonic basis
(y is the polar axis; within a degree-l block the 2l+1 components are
ordered m = -l..l with the m=0, y-aligned component at the center).

Everything here is DERIVED, not copied: the J tables are computed from
scratch by least squares against this repo's own spherical-harmonic
implementation (``ops/so3._sh_general``) evaluated in the e3nn axis
convention, and validated in-session against the reference's shipped
``Jd.pt`` to ~1e-15 for l <= 6 (the tables are pinned by a hardcoded l=1
check in tests/test_so3_e3nn.py; higher l follow from the representation
property, which the tests verify directly).

Basis relation: e3nn's real SH of degree l evaluated at (x, y, z) equals
the standard z-polar real SH evaluated at the cyclically permuted point
(z, x, y) — e.g. the l=1 triple comes out in (x, y, z) order with y (the
e3nn polar axis) at the m=0 center slot.

Angle convention (e3nn YXY): a unit vector u has beta = acos(u_y),
alpha = atan2(u_x, u_z); the rotation R(alpha, beta, 0) maps the polar
axis y-hat onto u, and its Wigner matrix D satisfies Y(R r) = D Y(r).
Hence D(alpha, beta, 0) rotates edge-frame coefficients to the lab frame
("wigner_inv" in fairchem terms) and its transpose rotates lab features
into the edge-aligned frame.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ..geometry import COORD_PRECISION
from .so3 import _sh_general


def sh_e3nn_np(l: int, r: np.ndarray) -> np.ndarray:
    """e3nn-convention real spherical harmonics (host, float64)."""
    r = np.asarray(r, dtype=np.float64)
    return _sh_general(l, r[..., [2, 0, 1]], np)


def _wigner_of_orthogonal_np(l: int, O: np.ndarray) -> np.ndarray:
    """D with Y(O r) = D Y(r) in the e3nn basis, by least squares."""
    rng = np.random.default_rng(12345)
    pts = rng.normal(size=(max(64, 4 * (2 * l + 1)), 3))
    pts /= np.linalg.norm(pts, axis=1, keepdims=True)
    Y = sh_e3nn_np(l, pts)
    Yo = sh_e3nn_np(l, pts @ np.asarray(O, dtype=np.float64).T)
    D, *_ = np.linalg.lstsq(Y, Yo, rcond=None)
    return D.T


# the orthogonal map whose per-l representation is the "Jd" table:
# (x, y, z) -> (-y, -x, z), i.e. the reflection swapping the alpha/gamma
# z-rotation axis (y) with the beta axis so X(beta) can be expressed in
# z-rotation form: J X_z(beta) J = X_x(beta)
_O_J = np.array([[0.0, -1.0, 0.0], [-1.0, 0.0, 0.0], [0.0, 0.0, 1.0]])


@functools.lru_cache(maxsize=None)
def jd_np(l: int) -> np.ndarray:
    """Derived per-l J table (involution; equals upstream Jd.pt values)."""
    return _wigner_of_orthogonal_np(l, _O_J)


def z_rot_np(l: int, angles: np.ndarray) -> np.ndarray:
    """Batched z-rotation (about e3nn's polar axis y) Wigner blocks.

    Frequencies run l..-l along the diagonal; sin terms sit on the
    antidiagonal. The diagonal is written last so the center element is
    cos(0) = 1, not sin(0) (reference escn_md.py's _z_rot_mat writes sin
    first for the same reason).
    """
    angles = np.asarray(angles, dtype=np.float64)
    K = 2 * l + 1
    f = np.arange(l, -l - 1, -1.0)
    M = np.zeros(angles.shape + (K, K))
    i = np.arange(K)
    M[..., i, K - 1 - i] = np.sin(f * angles[..., None])
    M[..., i, i] = np.cos(f * angles[..., None])
    return M


def _z_rot_jnp(l: int, angles):
    K = 2 * l + 1
    f = jnp.arange(l, -l - 1, -1.0, dtype=angles.dtype)
    co = jnp.cos(f * angles[..., None])  # (..., K)
    si = jnp.sin(f * angles[..., None])
    i = np.arange(K)
    M = jnp.zeros(angles.shape + (K, K), dtype=angles.dtype)
    M = M.at[..., i, K - 1 - i].set(si)
    M = M.at[..., i, i].set(co)
    return M


def edge_angles(rhat, eps: float = 1e-4):
    """e3nn (alpha, beta) of unit vectors, gradient-safe at the poles.

    At u = +-y-hat the azimuth is a pure gauge freedom, but atan2's gradient
    at (0, 0) is NaN and arccos's at +-1 is infinite — one pole-aligned edge
    (any ideal cubic crystal has them) would NaN the whole force array.
    Within ~eps of the pole the angle arguments are replaced by constants
    (alpha := 0, |cos beta| clipped to sqrt(1 - eps^2)): values are off by
    O(eps) only there, gradients flow zero through the substituted branch
    (a valid gauge choice), and everywhere else the computation is exact.
    """
    x, y, z = rhat[..., 0], rhat[..., 1], rhat[..., 2]
    rho2 = x * x + z * z
    safe = rho2 > (eps * eps)
    alpha = jnp.arctan2(jnp.where(safe, x, 0.0), jnp.where(safe, z, 1.0))
    # the clip limit must be STRICTLY below 1 in the working dtype — in
    # float32, 1 - eps^2/2 rounds to exactly 1.0 and arccos'(1) = -inf
    # would still NaN pole-aligned edges; nextafter guarantees >= 1 ulp
    npdt = np.dtype(rhat.dtype.name if hasattr(rhat, "dtype") else "float64")
    y_lim = float(np.nextafter(npdt.type(1.0 - eps * eps / 2),
                               npdt.type(0.0)))
    beta = jnp.arccos(jnp.clip(y, -y_lim, y_lim))
    return alpha, beta


def wigner_blocks_from_edges(l_max: int, rhat, gamma=None,
                             precision=COORD_PRECISION):
    """Per-l lab-from-edge Wigner blocks for a batch of edge directions.

    Returns ``[D_0, ..., D_lmax]`` with ``D_l``: (E, 2l+1, 2l+1) in the
    edge-directions' dtype. ``D_l @ f_edge`` rotates edge-frame
    coefficients to the lab frame; ``D_l.T @ f_lab`` rotates into the
    edge frame.

    ``gamma`` (default None = 0) is the per-edge gauge angle: the residual
    rotation about the edge axis, D(alpha, beta, gamma) = X(alpha) J
    X(beta) J X(gamma). The production path fixes gamma = 0 — the SO(2)
    convolutions are exactly gauge-covariant, so any gauge gives identical
    model output; fairchem instead carries the gamma implied by its
    edge_rot_mat construction (reference escn_md.py:99-109).
    tests/test_escn_md.py proves output invariance under random per-edge
    gamma AND under the construction-derived gamma of a fairchem-style
    edge frame, so the gamma=0 choice is certified, not assumed.

    ``precision`` is the matmul precision of the products that build a
    block: ``geometry.COORD_PRECISION`` unless the caller says otherwise. A
    float32 matmul is one bfloat16 pass on a TPU by default, and a block
    good to three digits is no rotation. A caller that casts the blocks to
    bfloat16 at every use loses those digits there anyway and may pass
    ``None``: at ``highest`` the hundred 5 x 5 products of an eSCN-MD step,
    batched over 32,768 edges, cost 3.2 % of it on a TPU v5e and moved the
    forces' error by nothing (PERF.md section 6, PR 28).
    """
    wdt = jnp.promote_types(rhat.dtype, jnp.float32)  # never bf16: the trig
    alpha, beta = edge_angles(rhat.astype(wdt))       # chains compound
    out = []
    for l in range(l_max + 1):
        J = jnp.asarray(jd_np(l), dtype=wdt)
        Xa = _z_rot_jnp(l, alpha)
        Xb = _z_rot_jnp(l, beta)
        D = jnp.einsum("epq,qr,ers,st->ept", Xa, J, Xb, J,
                       precision=precision)
        if gamma is not None:
            Xg = _z_rot_jnp(l, jnp.asarray(gamma, dtype=wdt))
            D = jnp.einsum("ept,etu->epu", D, Xg, precision=precision)
        out.append(D)
    return out


# ---------------------------------------------------------------------------
# Coefficient layout (lmax, mmax narrowing) — fairchem CoefficientMapping
# ---------------------------------------------------------------------------


class CoeffLayout:
    """Index bookkeeping for (l <= lmax, |m| <= min(l, mmax)) coefficients.

    The narrowed coefficient stack is l-major: for each l, the CENTER
    2*min(l, mmax)+1 rows of the (2l+1) e3nn block, order m = -mm..mm.
    ``plus_idx[m] / minus_idx[m]`` give, for each |m|, the narrowed-stack
    positions of the (l, +m) and (l, -m) coefficients over l = m..lmax —
    the (cos, sin) pairs the SO(2) convolutions mix (fairchem packs the
    same pairs via its to_m permutation, escn_md.py:117-129).
    ``signed_ms`` and ``piece_rows`` describe the same coefficients cut into
    one flat piece per signed m (models/escn_md.py), by static rows.
    """

    def __init__(self, l_max: int, m_max: int | None = None):
        self.l_max = l_max
        self.m_max = l_max if m_max is None else min(m_max, l_max)
        self.block_slices = []
        self.size = 0
        for l in range(l_max + 1):
            mm = min(l, self.m_max)
            self.block_slices.append(slice(self.size, self.size + 2 * mm + 1))
            self.size += 2 * mm + 1
        self.plus_idx, self.minus_idx = {}, {}
        for m in range(self.m_max + 1):
            plus, minus = [], []
            for l in range(m, l_max + 1):
                mm = min(l, self.m_max)
                base = self.block_slices[l].start
                plus.append(base + mm + m)    # center + m
                minus.append(base + mm - m)   # center - m
            self.plus_idx[m] = np.array(plus)
            self.minus_idx[m] = np.array(minus)
        self.signed_ms = [0] + [
            s * m for m in range(1, self.m_max + 1) for s in (1, -1)]

    def m_size(self, m: int) -> int:
        return self.l_max + 1 - m

    def block_rows(self, l: int) -> slice:
        """Rows of the full (2l+1) e3nn block kept after mmax narrowing."""
        mm = min(l, self.m_max)
        return slice(l - mm, l + mm + 1)

    def piece_rows(self, m: int) -> list[tuple[int, int]]:
        """``(l, row of narrowed block l)`` of the signed-m coefficients,
        l = |m|..lmax: the order in which a per-m piece's lanes run."""
        return [(l, min(l, self.m_max) + m)
                for l in range(abs(m), self.l_max + 1)]
