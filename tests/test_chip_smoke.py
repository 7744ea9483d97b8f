"""chip_smoke.py off the chip: it refuses to start, and its phases hold at
toy width on the 8-virtual-device CPU mesh with interpreter-mode kernels.
Plus the pieces of the chip path that a CPU can pin: where the compile
cache lands, the device-peak table, and a native build that fails loudly.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.tier1

# fp32 roundoff plus bf16 compute at toy width; the chip's own bands live
# in chip_smoke.BANDS with the measured values beside them
TOY_BANDS = {
    "bf16_vs_f32": {"dE_per_atom": (1e-2, None), "dF_rel": (0.2, None),
                    "dS_rel": (0.2, None)},
    "kernels_vs_xla": {"dE_per_atom": (1e-5, None), "dF_rel": (1e-4, None),
                       "dS_rel": (1e-4, None)},
    "net_force": (1e-3, None),
    "p4_vs_p1": {"dE_per_atom": (1e-3, None), "dF_rel": (0.1, None),
                 "dS_rel": (0.1, None)},
    "serve_vs_single": {"dE_per_atom": (1e-3, None), "dF_rel": (0.1, None),
                        "dS_rel": (0.1, None)},
}


def _toy_mace():
    from distmlip_tpu.models import MACE, MACEConfig

    model = MACE(MACEConfig(
        num_species=15, channels=8, l_max=2, a_lmax=2, hidden_lmax=1,
        correlation=2, num_interactions=2, num_bessel=4, radial_mlp=8,
        cutoff=3.0, avg_num_neighbors=12.0))
    return model, model.init(jax.random.PRNGKey(0))


def _run_smoke(**env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, **env), capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    """JAX_PLATFORMS=cpu python chip_smoke.py: non-zero, names what it
    found, prints no result."""
    r = _run_smoke(JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_refuses_interpreter_kernels():
    r = _run_smoke(DISTMLIP_KERNELS="interpret")
    assert r.returncode != 0 and "DISTMLIP_KERNELS" in r.stderr
    assert '"ok"' not in r.stdout


def test_verdict_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond ok/device and
    platform/kind/count; the summary (phases, claim) is the line before."""
    device = jax.devices()[0]
    out = json.loads(chip_smoke.verdict_line(device, len(jax.devices())))
    assert set(out) == {"ok", "device"} and out["ok"] is True
    assert set(out["device"]) == {"platform", "kind", "count"}
    assert out["device"] == {"platform": device.platform,
                             "kind": device.device_kind,
                             "count": len(jax.devices())}
    assert type(out["device"]["count"]) is int
    assert "\n" not in chip_smoke.verdict_line(device, 1)


def test_md_phases_at_toy_width():
    """MD-1 then MD-4 on the virtual mesh: zero compiles and rebuilds after
    step 1, four devices, collective-permutes in the compiled program,
    P=4 == P=1 and bf16 == f32 oracle inside the toy bands."""
    model, params = _toy_mace()
    watch = chip_smoke.CompileWatch()
    # 8 x 2 x 2 cells: 31.2 A / 4 slabs > 2 * (3.0 + 0.5) A
    atoms = chip_smoke.build_cell((8, 2, 2), seed=0)
    md1 = chip_smoke.phase_md1(model, params, atoms, watch, bands=TOY_BANDS,
                               steps=3, kernels="interpret")
    assert md1["executables_after_step_1"] == 0
    assert md1["rebuilds_after_step_1"] == 0
    assert len(md1["step_ms"]) == 3
    # Pallas call sites: MACE's two edge scans, none left on XLA
    assert md1["kernel_ops"]["segment_sum_into"] == [2, 0]
    md4 = chip_smoke.phase_md4(model, params, atoms, md1["result"], watch,
                               bands=TOY_BANDS, kernels="interpret")
    assert len(md4["devices"]) == 4
    assert sum(md4["collective_permutes"].values()) > 0
    assert md4["bytes_in_use"] is None   # CPU reports no memory stats


def test_md4_refuses_thin_slabs():
    model, params = _toy_mace()
    atoms = chip_smoke.build_cell((4, 2, 2), seed=0)   # 15.6 A / 4 < 7 A
    with pytest.raises(AssertionError, match="x extent"):
        chip_smoke.phase_md4(model, params, atoms, {}, None,
                             bands=TOY_BANDS)


def test_serve_phase_at_toy_width():
    model, params = _toy_mace()
    burst = [chip_smoke.build_cell((2, 2, 2), seed=10 + i) for i in range(8)]
    out = chip_smoke.phase_serve(model, params, burst,
                                 chip_smoke.CompileWatch(), bands=TOY_BANDS,
                                 kernels="interpret", timeout_s=300.0)
    assert out["compiles"] == 1 and out["completed"] == 8
    assert out["failed"] == 1 and out["poisoned_failed_with"] == "ValueError"


def test_kernels_phase_has_a_row_for_every_entry_of_the_default_table():
    """The KERNELS phase re-takes the measurement behind every entry of
    ``TPU_DEFAULT_MODE``; a row's kernel and XLA functions give one array
    of one shape (what the phase compares), traced here without running:
    the shapes are the published ones."""
    import jax.numpy as jnp

    from distmlip_tpu.kernels.dispatch import TPU_DEFAULT_MODE

    cases = dict(chip_smoke._kernel_cases(0))
    assert sorted(cases) == sorted(TPU_DEFAULT_MODE)
    kernel, xla, args = cases["wigner_rotate"](jnp.bfloat16)
    assert args[0].shape == (32768, 35) and args[0].dtype == jnp.float32
    got, want = jax.eval_shape(kernel, *args), jax.eval_shape(xla, *args)
    # five pieces of two operands, nine lab rows, the columns' cotangent
    assert got.shape == want.shape == (32768, (2 * 9 + 9) * 128 + 35)
    assert got.dtype == want.dtype == jnp.bfloat16
    # MACE's chunk into mace-md-1c's flat carry, UMA-S's into uma-md-1c's
    kernel, xla, args = cases["segment_sum_into"](jnp.bfloat16)
    assert [a.shape for a in args] == [(29568, 5120), (9856, 1152),
                                       (32768, 40, 128), (32768, 1152)]
    got, want = jax.eval_shape(kernel, *args), jax.eval_shape(xla, *args)
    assert got.shape == want.shape == (29568 * 5120 + 9856 * 1152,)
    assert got.dtype == want.dtype == jnp.bfloat16
    # a slab of dimenet-pp-md-1c's bond rows onto two rows a centre atom
    kernel, xla, args = cases["segment_repeat"](jnp.float32)
    assert [a.shape for a in args] == [(394368, 128)]
    got, want = jax.eval_shape(kernel, *args), jax.eval_shape(xla, *args)
    assert got.shape == want.shape == (2 * 8448, 128)
    # a scan chunk of eqv2-31m-md-1c's coefficients, forward and backward
    kernel, xla, args = cases["s2_activation"](jnp.bfloat16)
    assert [a.shape for a in args] == [(19, 64, 16384)] * 2
    got, want = jax.eval_shape(kernel, *args), jax.eval_shape(xla, *args)
    assert got.shape == want.shape == (2 * 19, 64, 16384)
    assert got.dtype == want.dtype == jnp.bfloat16


def test_chip_bands_carry_their_measurement():
    """Every band asserted on the chip has the measured value beside it,
    and the band is not tighter than what was measured."""
    def walk(node):
        if isinstance(node, dict):
            for v in node.values():
                yield from walk(v)
        else:
            yield node

    for band, measured in walk(chip_smoke.BANDS):
        assert measured is not None and 0 <= measured <= band


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

def _cache_dir_in_child(env):
    code = ("import json\n"
            "from distmlip_tpu.utils.compile_cache import "
            "enable_compile_cache\n"
            "import jax\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "path = enable_compile_cache()\n"
            "print(json.dumps([before, path, "
            "jax.config.jax_compilation_cache_dir]))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_respects_the_environment(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    before, path, after = _cache_dir_in_child(env)
    # jax read the variable itself; the helper set nothing in code
    assert before == path == after == str(tmp_path)


def test_compile_cache_defaults_inside_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    before, path, after = _cache_dir_in_child(env)
    assert before is None
    assert path == after == os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("placed", [True, False],
                         ids=["from-outside", "in-checkout"])
def test_compile_cache_keys_executables_by_their_metadata(tmp_path, placed):
    """An executable from the cache carries the scope names of the code
    that compiled it, and the stage tables read them: with either
    placement the key holds the metadata, so changed scopes compile anew."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax\n"
            "from distmlip_tpu.utils.compile_cache import "
            "enable_compile_cache\n"
            "name = 'jax_compilation_cache_include_metadata_in_key'\n"
            "before = getattr(jax.config, name)\n"
            "enable_compile_cache()\n"
            "print(before, getattr(jax.config, name))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-2:] == ["False", "True"]


# ---------------------------------------------------------------------------
# device peaks
# ---------------------------------------------------------------------------

class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peak_table_is_keyed_by_exact_device_kind():
    from distmlip_tpu.utils.flops import (device_peaks, mfu,
                                          peak_flops_per_device)

    v5e = _Device("tpu", "TPU v5 lite")
    assert peak_flops_per_device(v5e) == 197e12
    assert device_peaks(v5e) == (197e12, 819e9)
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        peak_flops_per_device(_Device("tpu", "TPU v9 imaginary"))
    # a substring of a known kind is not a match
    with pytest.raises(ValueError):
        peak_flops_per_device(_Device("tpu", "TPU v5"))
    assert peak_flops_per_device(_Device("cpu", "cpu")) is None
    assert peak_flops_per_device() is None     # this test runs on CPU
    assert mfu(1e12, 1.0, 1) is None           # so MFU is not computed


# ---------------------------------------------------------------------------
# native build
# ---------------------------------------------------------------------------

def test_failed_native_build_raises(tmp_path, monkeypatch):
    """A compiler error reaches the caller with the compiler's stderr; the
    numpy search is not a silent second path."""
    from distmlip_tpu.neighbors import native

    src = tmp_path / "src"
    src.mkdir()
    (src / "broken.cpp").write_text("this is not C++\n")
    (src / "Makefile").write_text(
        "all:\n\tg++ -shared -fPIC -o ../_native.so broken.cpp\n")
    monkeypatch.setattr(native, "_SRC_DIR", str(src))
    monkeypatch.setattr(native, "_LIB_PATH", str(tmp_path / "_native.so"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.delenv("DISTMLIP_TPU_NATIVE_LIB", raising=False)
    cart = np.random.default_rng(0).random((8, 3)) * 6.0
    with pytest.raises(native.NativeBuildError, match="broken.cpp"):
        native.neighbor_list(cart, np.eye(3) * 6.0, [1, 1, 1], 2.5)
    # an unloadable library raises too
    (tmp_path / "_native.so").write_bytes(b"not an ELF file")
    os.utime(tmp_path / "_native.so", (2e9, 2e9))   # newer than the sources
    with pytest.raises(native.NativeBuildError, match="cannot load"):
        native.neighbor_list(cart, np.eye(3) * 6.0, [1, 1, 1], 2.5)


# ---------------------------------------------------------------------------
# coordinates multiply at full precision
# ---------------------------------------------------------------------------

def test_coordinate_contractions_carry_highest_precision():
    """A TPU multiplies float32 matmuls as one bf16 pass by default, which
    moved periodic images by 0.05 A and cost 24-89 % force error on the
    chip (PR 21). A CPU cannot see the error, but it can see the fix: every
    contraction over a length-3 coordinate axis in the single-structure,
    the batched and the on-device-neighbour programs asks for HIGHEST —
    forward and transposed."""
    from distmlip_tpu.analysis import ir
    from distmlip_tpu.calculators import BatchedPotential, DistPotential
    from distmlip_tpu.models.pair import PairConfig, PairPotential
    from distmlip_tpu.neighbors.device import (build_cell_list_spec,
                                               cell_list_neighbors)

    model = PairPotential(PairConfig(cutoff=3.0))
    params = model.init(jax.random.PRNGKey(0))
    atoms = chip_smoke.build_cell((2, 2, 2), seed=0)
    single = DistPotential(model, params, num_partitions=1, skin=0.5)
    single.calculate(atoms)
    graph = single._cache[0]
    batched = BatchedPotential(model, params, skin=0.5)
    batched.calculate([atoms, atoms.copy()])
    bgraph = batched._cache[0]
    static, arrays = build_cell_list_spec(
        atoms.cell, atoms.pbc, 3.5, len(atoms), len(atoms), 4096,
        positions=atoms.positions)
    programs = {
        "single": jax.make_jaxpr(single._potential)(
            params, graph, graph.positions),
        "batched": jax.make_jaxpr(batched._potential)(
            params, bgraph, bgraph.positions),
        "neighbours": jax.make_jaxpr(
            lambda p: cell_list_neighbors(static, arrays, p))(
                np.asarray(atoms.positions, np.float32)),
    }
    highest = jax.lax.Precision.HIGHEST
    for name, jaxpr in programs.items():
        dots = [e for e in ir.iter_eqns(jaxpr)
                if e.primitive.name == "dot_general"]
        assert dots, name     # the pair model has no other matmuls
        for eqn in dots:
            assert eqn.params["precision"] == (highest, highest), (
                name, eqn.invars[0].aval, eqn.invars[1].aval)
