"""Real spherical harmonics and coupling tensors for the plain references.

A copy of the construction in ``distmlip_tpu/ops/so3.py`` (listed in PERF.md
under Open questions), kept here so that the reference imports nothing of
the program and makes its own tables. Host side is numpy float64; the
device side takes the array module as an argument.

Conventions: unit vectors ordered (x, y, z); component normalisation
(``|Y_l|^2 = 2l+1``); m runs -l..l in e3nn order. Only l <= 3 is tabulated,
which is all MACE-MP-0 needs.
"""

from __future__ import annotations

import os
from functools import lru_cache
from itertools import combinations_with_replacement, permutations

import numpy as np


def spherical_harmonics(l: int, u, xp=np):
    x, y, z = u[..., 0], u[..., 1], u[..., 2]
    if l == 0:
        return xp.ones(u.shape[:-1] + (1,), dtype=u.dtype)
    if l == 1:
        s3 = float(np.sqrt(3.0))
        return xp.stack([s3 * x, s3 * y, s3 * z], axis=-1)
    if l == 2:
        s15, s5 = float(np.sqrt(15.0)), float(np.sqrt(5.0))
        return xp.stack([
            s15 * x * y,
            s15 * y * z,
            s5 / 2.0 * (3.0 * z * z - 1.0),
            s15 * x * z,
            s15 / 2.0 * (x * x - y * y),
        ], axis=-1)
    if l == 3:
        s = lambda v: float(np.sqrt(v))
        return xp.stack([
            s(35.0 / 8.0) * y * (3 * x * x - y * y),
            s(105.0) * x * y * z,
            s(21.0 / 8.0) * y * (5 * z * z - 1.0),
            s(7.0) / 2.0 * z * (5 * z * z - 3.0),
            s(21.0 / 8.0) * x * (5 * z * z - 1.0),
            s(105.0) / 2.0 * z * (x * x - y * y),
            s(35.0 / 8.0) * x * (x * x - 3 * y * y),
        ], axis=-1)
    raise ValueError(f"spherical harmonics are tabulated to l = 3, not {l}")


def wigner_d(l: int, rot: np.ndarray) -> np.ndarray:
    """Real Wigner matrix with Y_l(R u) = D_l(R) Y_l(u), by least squares
    over fixed sample points."""
    rng = np.random.default_rng(12345)
    pts = rng.normal(size=(max(64, 4 * (2 * l + 1)), 3))
    pts /= np.linalg.norm(pts, axis=1, keepdims=True)
    y = spherical_harmonics(l, pts)
    yr = spherical_harmonics(l, pts @ np.asarray(rot, dtype=np.float64).T)
    d, *_ = np.linalg.lstsq(y, yr, rcond=None)
    return d.T


def _random_rotation(rng) -> np.ndarray:
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    if np.linalg.det(q) < 0:
        q[:, 0] *= -1
    return q


@lru_cache(maxsize=None)
def clebsch_gordan(l1: int, l2: int, l3: int) -> np.ndarray:
    """Real coupling tensor (2l1+1, 2l2+1, 2l3+1): the one invariant of
    D_l1 x D_l2 x D_l3, normalised to sum(C^2) = 2 l3 + 1, first
    significant entry positive."""
    if not abs(l1 - l2) <= l3 <= l1 + l2:
        raise ValueError(f"({l1}, {l2}, {l3}) breaks the triangle rule")
    d1, d2, d3 = 2 * l1 + 1, 2 * l2 + 1, 2 * l3 + 1
    d = d1 * d2 * d3
    rng = np.random.default_rng(2024)
    rows = []
    for _ in range(4):
        rot = _random_rotation(rng)
        full = np.einsum("xa,yb,zc->xyzabc", wigner_d(l1, rot),
                         wigner_d(l2, rot), wigner_d(l3, rot))
        rows.append(full.reshape(d, d) - np.eye(d))
    _, s, vt = np.linalg.svd(np.vstack(rows), full_matrices=False)
    if s[-1] > 1e-6 or (len(s) > 1 and s[-2] < 1e-4):
        raise RuntimeError(f"coupling ({l1},{l2},{l3}): singular values "
                           f"{s[-3:]} do not show one invariant")
    c = vt[-1].reshape(d1, d2, d3)
    flat = c.ravel()
    if flat[np.argmax(np.abs(flat) > 0.1 * np.abs(flat).max())] < 0:
        c = -c
    return np.ascontiguousarray(c * np.sqrt(d3) / np.sqrt((c ** 2).sum()))


def symmetric_basis(a_ls: tuple, l_out: int, nu: int,
                    cache_dir: str | None = None):
    """Orthonormal basis U of the O(3)-equivariant, totally symmetric maps
    Sym^nu(V_A) -> V_l_out (V_A the direct sum of the irreps in ``a_ls``
    with SH parity): shape (S_A,)*nu + (2 l_out + 1, n_paths), or None
    where the space is empty. Any two such bases differ by an orthogonal
    mixing of the path axis; the harness carries weights across that
    mixing (benchmark/harness/weights.py), so this one need not be the
    program's. ``cache_dir`` keeps the result on disk (the nu = 3 bases
    take about a minute)."""
    a_ls = tuple(a_ls)
    path = None
    if cache_dir is not None:
        path = os.path.join(
            cache_dir, f"U_{'-'.join(map(str, a_ls))}_{l_out}_{nu}.npy")
        if os.path.exists(path):
            arr = np.load(path)
            return None if arr.size == 0 else arr
    u = _symmetric_basis(a_ls, l_out, nu)
    if path is not None:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.npy"
        np.save(tmp, u if u is not None else np.zeros(0))
        os.replace(tmp, path)
    return u


def _symmetric_basis(a_ls: tuple, l_out: int, nu: int):
    s_a = sum(2 * l + 1 for l in a_ls)
    d_out = 2 * l_out + 1
    lvals = np.concatenate([[l] * (2 * l + 1) for l in a_ls]).astype(int)
    idxs = list(combinations_with_replacement(range(s_a), nu))
    dim_sym, full = len(idxs), s_a ** nu

    # embedding of the symmetric tensors into the full tensor space
    emb = np.zeros((full, dim_sym))
    strides = np.array([s_a ** (nu - 1 - i) for i in range(nu)])
    for a, alpha in enumerate(idxs):
        perms = set(permutations(alpha))
        for p in perms:
            emb[int(np.dot(p, strides)), a] = 1.0 / np.sqrt(len(perms))

    def d_full(rot):
        d = np.zeros((s_a, s_a))
        o = 0
        for l in a_ls:
            d[o:o + 2 * l + 1, o:o + 2 * l + 1] = wigner_d(l, rot)
            o += 2 * l + 1
        out = d
        for _ in range(nu - 1):
            out = np.kron(out, d)
        return out

    rng = np.random.default_rng(7041)
    dim_c = dim_sym * d_out
    rows = []
    for _ in range(3):
        rot = _random_rotation(rng)
        rows.append(np.kron(emb.T @ d_full(rot) @ emb, wigner_d(l_out, rot))
                    - np.eye(dim_c))
    # inversion: D_l(-1) = (-1)^l per block, which drops odd-parity paths
    parity = np.asarray([
        (-1.0) ** lvals.take(np.unravel_index(i, (s_a,) * nu)).sum()
        for i in range(full)])
    rows.append(np.kron(emb.T @ np.diag(parity) @ emb,
                        np.eye(d_out) * (-1.0) ** l_out) - np.eye(dim_c))
    _, s, vt = np.linalg.svd(np.vstack(rows), full_matrices=True)
    n_paths = int(np.sum(s < 1e-8))
    if n_paths == 0:
        return None
    if n_paths < dim_c and s[dim_c - n_paths - 1] < 1e-5:
        raise RuntimeError(f"symmetric basis ({a_ls}, {l_out}, {nu}): "
                           f"borderline singular value")
    null = vt[-n_paths:].reshape(n_paths, dim_sym, d_out)
    u = emb @ null.transpose(1, 2, 0).reshape(dim_sym, -1)
    return np.ascontiguousarray(u.reshape((s_a,) * nu + (d_out, n_paths)))
