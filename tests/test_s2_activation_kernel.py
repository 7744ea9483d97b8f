"""EquiformerV2's edge S2 activation as one Pallas pass a direction
(``kernels/dispatch.fused_s2_activation`` over ``so3.s2_activation_pallas``)
against the two XLA products it replaces, in interpret mode at toy sizes:
the forward, the VJP against autodiff of the XLA form at float32 and
bfloat16, at edge counts inside one lane block and over two, and the
model's l = 0 replacement outside the op.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distmlip_tpu.kernels import counting
from distmlip_tpu.kernels.dispatch import fused_s2_activation
from distmlip_tpu.kernels.so3 import S2_LANES, s2_lanes, s2_tables
from distmlip_tpu.models import EquiformerV2, EquiformerV2Config

H = 8
EDGE_GRID = EquiformerV2(EquiformerV2Config(
    sphere_channels=8, attn_hidden_channels=H, num_layers=1)).edge_grid
# bfloat16 agrees to a few units of its last place: the products sum in
# another order, and a grid value or SiLU's can round the other way
TOL = {"float32": 2e-6, "bfloat16": 2e-2}


def rows(e, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(e, 19, H)), dtype)


def activation(mode):
    return jax.jit(lambda x: fused_s2_activation(x, *EDGE_GRID, kernels=mode))


def close(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.abs(got - want).max() <= TOL[dtype] * np.abs(want).max()


def test_tables_are_zero_padded_to_whole_tiles():
    G, Gt, F, Ft = s2_tables(*EDGE_GRID, np.float32)
    assert G.shape == Ft.shape == (336, 19) and F.shape == Gt.shape == (19, 336)
    np.testing.assert_array_equal(G[:324], EDGE_GRID[0])
    np.testing.assert_array_equal(F[:, :324], EDGE_GRID[1])
    assert not G[324:].any() and not F[:, 324:].any()
    np.testing.assert_array_equal(Gt, G.T)
    np.testing.assert_array_equal(Ft, F.T)
    # a block of edges: whole 128-lane tiles, at most S2_LANES at 2 bytes
    # an element and half as many at 4
    assert s2_lanes(S2_LANES + 52, jnp.bfloat16) == S2_LANES
    assert s2_lanes(S2_LANES + 52, jnp.float32) == S2_LANES // 2
    assert s2_lanes(200, jnp.bfloat16) == s2_lanes(200, jnp.float32) == 256


# 100 edges: one block of 128 lanes, partly padding; S2_LANES + 52: two
# blocks in bfloat16 and three in float32, the last mostly padding
@pytest.mark.parametrize("edges", [100, S2_LANES + 52])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_and_vjp_agree_with_the_xla_products(edges, dtype):
    x, dy = rows(edges, dtype, 0), rows(edges, dtype, 1)
    out = {}
    for mode in ("xla", "interpret"):
        with counting() as c:
            y, vjp = jax.vjp(activation(mode), x)
        assert c.ops == {"s2_activation": [mode != "xla", mode == "xla"]}
        (dx,) = vjp(dy)
        assert y.dtype == dx.dtype == x.dtype and y.shape == x.shape
        out[mode] = y, dx
    close(out["interpret"][0], out["xla"][0], dtype)
    close(out["interpret"][1], out["xla"][1], dtype)


def test_the_kernel_path_matches_float32_products_at_highest():
    """Against float32 products at highest precision the bfloat16 kernel
    is as close as the bfloat16 XLA path is: its rounding is the
    configuration's."""
    x32, dy32 = rows(300, "float32", 2), rows(300, "float32", 3)
    with jax.default_matmul_precision("highest"):
        y_ref, vjp = jax.vjp(activation("xla"), x32)
        dx_ref = vjp(dy32)[0]
    errs = {}
    for mode in ("xla", "interpret"):
        y, vjp = jax.vjp(activation(mode), x32.astype(jnp.bfloat16))
        dx = vjp(dy32.astype(jnp.bfloat16))[0]
        errs[mode] = [float(np.abs(np.asarray(a, np.float32) - b).max()
                            / np.abs(b).max())
                      for a, b in ((y, y_ref), (dx, dx_ref))]
    for got, xla in zip(errs["interpret"], errs["xla"]):
        assert got <= 1.5 * xla + 1e-3


def test_the_l0_row_is_replaced_outside_the_op():
    """The model sets the activation's l = 0 coefficient to SiLU of the
    gate after the op: a cotangent on that row reaches the gate and nothing
    of the pieces, on either path, and the two paths agree on the whole."""
    model = EquiformerV2(EquiformerV2Config(
        sphere_channels=8, attn_hidden_channels=H, num_layers=1))
    lay, dtype = model.lay, "bfloat16"
    rng = np.random.default_rng(4)
    e = 120
    y = {m: jnp.asarray(rng.normal(size=(e, lay.m_size(abs(m)) * H)), dtype)
         for m in lay.signed_ms}
    gate = jnp.asarray(rng.normal(size=(e, H)), dtype)
    ct = {m: jnp.asarray(rng.normal(size=f.shape), dtype)
          for m, f in y.items()}
    grads = {}
    for mode in ("xla", "interpret"):
        out, vjp = jax.vjp(jax.jit(
            lambda y_, g_: model._s2_activation(y_, g_, mode)), y, gate)
        np.testing.assert_array_equal(
            np.asarray(out[0][:, :H], np.float32),
            np.asarray(jax.nn.silu(gate), np.float32))
        # a cotangent on the l = 0 row alone: the pieces get exactly zero
        only_l0 = {m: jnp.zeros_like(f) for m, f in out.items()}
        only_l0[0] = only_l0[0].at[:, :H].set(1)
        dy, dgate = vjp(only_l0)
        assert all(not np.asarray(d).any() for d in dy.values())
        assert np.asarray(dgate).any()
        grads[mode] = vjp(ct)
    for a, b in zip(jax.tree.leaves(grads["interpret"]),
                    jax.tree.leaves(grads["xla"])):
        close(a, b, dtype)
