"""Aux subsystems: checkpoint save/load, profiling, torch conversion machinery."""

import numpy as np
import pytest

import jax

from distmlip_tpu.models import TensorNet, TensorNetConfig
from distmlip_tpu.models.convert import Rule, convert
from distmlip_tpu.utils.checkpoint import load_params, save_params
from distmlip_tpu.utils.profiling import StepTimer


def test_checkpoint_roundtrip(tmp_path):
    model = TensorNet(TensorNetConfig(num_species=4, units=8, num_rbf=4, num_layers=1))
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "ckpt.npz")
    save_params(path, params)
    restored = load_params(path, like=params)
    leaves1 = jax.tree.leaves(params)
    leaves2 = jax.tree.leaves(restored)
    assert len(leaves1) == len(leaves2)
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # structure preserved (lists stay lists)
    assert isinstance(restored["layers"], list)


def test_checkpoint_shape_mismatch(tmp_path):
    model = TensorNet(TensorNetConfig(num_species=4, units=8, num_rbf=4, num_layers=1))
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "ckpt.npz")
    save_params(path, params)
    other = TensorNet(TensorNetConfig(num_species=4, units=16, num_rbf=4, num_layers=1))
    with pytest.raises(ValueError, match="shape mismatch"):
        load_params(path, like=other.init(jax.random.PRNGKey(0)))


def test_convert_rules():
    params = {"lin": {"w": np.zeros((3, 2)), "b": np.zeros(2)}}
    sd = {"layer.weight": np.arange(6.0).reshape(2, 3), "layer.bias": np.ones(2)}
    out, report = convert(
        sd, params,
        [Rule("layer.weight", ("lin", "w"), lambda a: a.T),
         Rule("layer.bias", ("lin", "b"))],
    )
    np.testing.assert_allclose(out["lin"]["w"], np.arange(6.0).reshape(2, 3).T)
    assert report["mapped"] == 2 and not report["unused_torch"]


def test_convert_strict_unused():
    params = {"lin": {"w": np.zeros((1, 1))}}
    sd = {"a.weight": np.zeros((1, 1)), "extra": np.zeros(3)}
    with pytest.raises(ValueError, match="unmapped"):
        convert(sd, params, [Rule("a.weight", ("lin", "w"), lambda a: a.T)])


def test_step_timer():
    t = StepTimer()
    with t.phase("x"):
        pass
    t.add({"y": 0.5})
    s = t.summary()
    assert "x" in s and "y" in s


def test_checkpoint_none_leaves_roundtrip(tmp_path):
    """None leaves (empty subtrees, e.g. ESCN mole_gate with 1 expert) must
    round-trip without pickled object arrays (ADVICE r1)."""
    params = {"a": {"w": np.ones((2, 2))}, "gate": None,
              "layers": [{"w": np.zeros(3), "opt": None}]}
    path = str(tmp_path / "ckpt_none.npz")
    save_params(path, params)
    restored = load_params(path, like=params)
    assert restored["gate"] is None
    assert restored["layers"][0]["opt"] is None
    np.testing.assert_allclose(restored["a"]["w"], params["a"]["w"])


def test_checkpoint_escn_roundtrip(tmp_path):
    """Full ESCN params (num_experts=1 -> mole_gate=None) round-trip."""
    from distmlip_tpu.models import ESCN, ESCNConfig

    model = ESCN(ESCNConfig(num_species=3, channels=8, l_max=1, num_layers=1,
                            num_bessel=4, num_experts=1))
    params = model.init(jax.random.PRNGKey(1))
    path = str(tmp_path / "escn.npz")
    save_params(path, params)
    restored = load_params(path, like=params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_layout_version_gate(tmp_path):
    """A checkpoint without the layout-version sentinel (pre-channels-last
    era) must be refused by default — shapes match across the flip, so a
    silent load would compute wrong energies (ADVICE r3)."""
    import numpy as np
    import pytest

    from distmlip_tpu.utils import checkpoint as ckpt

    params = {"a": {"w": np.arange(6.0).reshape(2, 3)}}
    legacy = tmp_path / "legacy.npz"
    np.savez_compressed(legacy, **ckpt._flatten_with_paths(params))
    with pytest.raises(ValueError, match="layout version"):
        ckpt.load_params(str(legacy), like=params)
    back = ckpt.load_params(str(legacy), like=params, allow_legacy_layout=True)
    np.testing.assert_array_equal(back["a"]["w"], params["a"]["w"])
    # current-era saves round-trip and the sentinel never leaks into trees
    cur = tmp_path / "cur.npz"
    ckpt.save_params(str(cur), params)
    assert ckpt._LAYOUT_KEY not in ckpt.load_params(str(cur))


def test_checkpoint_namedtuple_roundtrip(tmp_path):
    """Optax optimizer states are NamedTuples: save/load must reconstruct
    them positionally (train.save/load_train_state relies on this)."""
    import numpy as np
    import optax

    from distmlip_tpu.utils.checkpoint import load_params, save_params

    params = {"w": np.ones((3, 2), np.float32)}
    opt = optax.adam(1e-3)
    state = opt.init(params)
    path = tmp_path / "state.npz"
    save_params(str(path), {"opt": state})
    back = load_params(str(path), like={"opt": state})
    assert type(back["opt"]) is type(state)
    import jax

    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back["opt"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
