"""The Wigner rotation kernels (``kernels/so3.py``: lab rows <-> the edge
frame's per-m pieces, block entries as per-edge float32 columns) against the
batched per-l products they replace on the TPU, in interpret mode on the CPU:
values, gradients with respect to rows AND columns, inside a scanned and
checkpointed body, and which path the dispatch takes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.kernels import counting
from distmlip_tpu.kernels.dispatch import fused_wigner_rotate
from distmlip_tpu.kernels.so3 import wigner_cols, wigner_n_cols
from distmlip_tpu.ops.so3_e3nn import CoeffLayout, wigner_blocks_from_edges

C = 128
LAYOUTS = [(2, 2), (2, 1), (3, 2)]


def operands(lmax, mmax, E, dtype, seed=31, c=C, present=None):
    """Columns of real Wigner blocks, two lab operands, the ``present``
    pieces (default: all) with ``c`` lanes a degree, and a cotangent for
    each side."""
    lay = CoeffLayout(lmax, mmax)
    rng = np.random.default_rng(seed)
    rhat = rng.normal(size=(E, 3))
    rhat /= np.linalg.norm(rhat, axis=1, keepdims=True)
    cols = wigner_cols(wigner_blocks_from_edges(
        lmax, jnp.asarray(rhat, jnp.float32)))
    S = (lmax + 1) ** 2
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    labs = (arr(E, S * c), arr(E, S * c))
    ms = lay.signed_ms if present is None else present
    pieces = {m: arr(E, lay.m_size(abs(m)) * c) for m in ms}
    g_edge = {m: arr(E, lay.m_size(abs(m)) * 2 * c) for m in lay.signed_ms}
    return lay, cols, labs, pieces, g_edge, arr(E, S * c)


def rotate_both(lay, kernels):
    """``(cols, labs, pieces) -> (edge pieces of the labs, lab rows of the
    pieces)`` on one dispatch path."""
    def f(cols, labs, pieces):
        return (fused_wigner_rotate(cols, labs, lay, to_edge=True,
                                    kernels=kernels),
                fused_wigner_rotate(cols, pieces, lay, to_edge=False,
                                    kernels=kernels))
    return f


def f32(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def assert_close(got, want, tol):
    for g, w in zip(jax.tree.leaves(f32(got)), jax.tree.leaves(f32(want)),
                    strict=True):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=tol * max(1.0, np.abs(w).max()),
                                   rtol=0)


@pytest.mark.parametrize("E", [200, 300])   # neither is a multiple of a tile
@pytest.mark.parametrize("lmax, mmax", LAYOUTS)
def test_kernels_equal_the_batched_products_in_float32(lmax, mmax, E):
    lay, cols, labs, pieces, _, _ = operands(lmax, mmax, E, jnp.float32)
    with counting() as n:
        got = rotate_both(lay, "interpret")(cols, labs, pieces)
    assert n.ops == {"wigner_rotate": [2, 0]}
    want = rotate_both(lay, False)(cols, labs, pieces)
    assert sorted(got[0]) == sorted(lay.signed_ms)
    assert got[1].shape == (E, (lmax + 1) ** 2 * C)
    assert_close(got, want, 2e-6)


@pytest.mark.parametrize("lmax, mmax", LAYOUTS)
def test_kernels_round_no_more_than_the_batched_products_in_bfloat16(
        lmax, mmax):
    """bfloat16 rows: against the float32 products of the same (upcast)
    operands the kernel is within one rounding of its bfloat16 result, and
    no further off than the path that casts the blocks to bfloat16."""
    lay, cols, labs, pieces, _, _ = operands(lmax, mmax, 200, jnp.bfloat16)
    got = rotate_both(lay, "interpret")(cols, labs, pieces)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(got))
    cast = rotate_both(lay, False)(cols, labs, pieces)
    exact = rotate_both(lay, False)(
        cols, *jax.tree.map(lambda x: x.astype(jnp.float32), (labs, pieces)))
    assert_close(got, exact, 2.0 ** -8)
    err = lambda t: sum(float(np.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(f32(t)), jax.tree.leaves(f32(exact))))
    assert err(got) <= 1.01 * err(cast)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lmax, mmax", LAYOUTS)
def test_an_absent_piece_is_skipped(lmax, mmax, dtype):
    """The edge-degree embedding hands over the m = 0 piece alone."""
    dtype = jnp.dtype(dtype)
    lay, cols, _, pieces, _, g_lab = operands(lmax, mmax, 200, dtype,
                                              present=[0])
    run = lambda k: jax.value_and_grad(lambda c, y: jnp.sum(
        fused_wigner_rotate(c, y, lay, to_edge=False, kernels=k)
        .astype(jnp.float32) * g_lab.astype(jnp.float32)), argnums=(0, 1))(
            cols, pieces)
    (v, (dc, dy)), (v0, (dc0, dy0)) = run("interpret"), run(False)
    tol = 2e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(v, v0, rtol=tol * 10)
    assert_close((dc, dy), (dc0, dy0), tol)
    # columns that only the absent pieces read have no cotangent
    from distmlip_tpu.kernels.so3 import wigner_col
    read = {wigner_col(l, p, l) for l in range(lmax + 1)
            for p in range(2 * l + 1)}
    unread = sorted(set(range(wigner_n_cols(lmax))) - read)
    assert unread and not np.asarray(dc)[:, unread].any()


def loss_of(lay, kernels, g_edge, g_lab):
    def loss(cols, labs, pieces):
        fr, out = rotate_both(lay, kernels)(cols, labs, pieces)
        up = lambda x: x.astype(jnp.float32)
        return (sum(jnp.sum(up(fr[m]) * up(g_edge[m])) for m in fr)
                + jnp.sum(up(out) * up(g_lab)))
    return loss


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lmax, mmax", LAYOUTS)
def test_gradients_of_rows_and_blocks_equal_autodiff_of_the_products(
        lmax, mmax, dtype):
    dtype = jnp.dtype(dtype)
    lay, cols, labs, pieces, g_edge, g_lab = operands(lmax, mmax, 200, dtype)
    grads = lambda k, *a: jax.grad(loss_of(lay, k, g_edge, g_lab),
                                   argnums=(0, 1, 2))(*a)
    got = grads("interpret", cols, labs, pieces)
    assert got[0].dtype == jnp.float32 and got[0].shape == cols.shape
    assert all(x.dtype == dtype for x in jax.tree.leaves(got[1:]))
    if dtype == jnp.float32:
        assert_close(got, grads(False, cols, labs, pieces), 1e-5)
        return
    # the blocks' cotangent sums exact products of bfloat16 numbers in
    # float32: it equals the float32 path on the upcast operands
    up = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    g_edge, g_lab = up(g_edge), up(g_lab)
    exact = jax.grad(loss_of(lay, False, g_edge, g_lab), argnums=(0, 1, 2))(
        cols, up(labs), up(pieces))
    assert_close(got[0], exact[0], 1e-5)
    assert_close(got[1:], exact[1:], 2.0 ** -8)


@pytest.mark.parametrize("lmax, mmax", LAYOUTS)
def test_gradients_equal_central_differences(lmax, mmax):
    lay, cols, labs, pieces, g_edge, g_lab = operands(
        lmax, mmax, 40, jnp.float32)
    loss = loss_of(lay, "interpret", g_edge, g_lab)
    args = (cols, labs, pieces)
    grads = jax.grad(loss, argnums=(0, 1, 2))(*args)
    rng = np.random.default_rng(5)
    dirs = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), args)
    step = lambda t: jax.tree.map(lambda x, v: x + t * v, args, dirs)
    # bilinear in (columns, rows): quadratic along a joint direction, so the
    # central difference is exact up to float32 rounding at any step
    h = 1e-2
    fd = (loss(*step(h)) - loss(*step(-h))) / (2 * h)
    want = sum(float(jnp.vdot(g, v)) for g, v in zip(
        jax.tree.leaves(grads), jax.tree.leaves(dirs)))
    np.testing.assert_allclose(fd, want, rtol=2e-3)


def test_the_call_survives_scan_under_checkpoint_and_a_second_derivative():
    """As the model calls it: inside ``lax.scan`` with the chunk body under
    ``jax.checkpoint``; and the backward is itself differentiable (a force
    loss differentiates through the forces)."""
    lay, cols, labs, pieces, g_edge, g_lab = operands(2, 2, 128, jnp.float32)
    K = 2
    chunk = lambda x: x.reshape((K, -1) + x.shape[1:])

    def total(kernels):
        loss = loss_of(lay, kernels, jax.tree.map(lambda x: x[:64], g_edge),
                       g_lab[:64])

        def f(cols, labs, pieces):
            def body(acc, xs):
                return acc + jax.checkpoint(loss)(*xs), None
            return jax.lax.scan(body, jnp.float32(0.0), jax.tree.map(
                chunk, (cols, labs, pieces)))[0]
        return f

    args = (cols, labs, pieces)
    got = jax.jit(jax.value_and_grad(total("interpret"), argnums=(0, 1, 2)))(
        *args)
    want = jax.value_and_grad(total(False), argnums=(0, 1, 2))(*args)
    assert_close(got, want, 1e-5)

    def force_loss(kernels):
        return lambda c, x, y: jnp.sum(jax.grad(
            loss_of(lay, kernels, g_edge, g_lab))(c, x, y) ** 2)

    small = jax.tree.map(lambda x: x[:32], (cols, labs, pieces, g_edge,
                                            g_lab))
    cols, labs, pieces, g_edge, g_lab = small
    got2 = jax.grad(force_loss("interpret"), argnums=(1, 2))(*small[:3])
    want2 = jax.grad(force_loss(False), argnums=(1, 2))(*small[:3])
    assert_close(got2, want2, 1e-5)


@pytest.mark.parametrize("c, pallas", [(64, 0), (128, 1), (256, 1)])
def test_the_kernel_path_is_taken_at_whole_lane_tiles_only(c, pallas):
    """``C % 128 == 0`` is what the dispatch can see in its operand; any
    other width takes the batched products, and the counter says so."""
    lay, cols, labs, pieces, _, _ = operands(2, 2, 64, jnp.float32, c=c)
    with counting() as n:
        got = rotate_both(lay, "interpret")(cols, labs, pieces)
    assert n.ops == {"wigner_rotate": [2 * pallas, 2 * (1 - pallas)]}
    assert_close(got, rotate_both(lay, False)(cols, labs, pieces), 2e-6)
    with counting() as n:
        rotate_both(lay, None)(cols, labs, pieces)   # the CPU's default
    assert n.ops == {"wigner_rotate": [0, 2]}
