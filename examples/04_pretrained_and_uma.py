"""Pretrained-weight ingestion + UMA-style conditioned inference.

Mirrors the reference's examples/mace_example.ipynb + uma_example.ipynb flow
(from_existing -> enable_distributed_mode -> calculate) in the TPU-native
workflow:

  1. Export a mace-torch checkpoint ONCE in an environment that has
     mace-torch installed:
         python -m distmlip_tpu.tools.export_upstream mace mace.model mace.npz
  2. Anywhere (this environment): load the npz, map it onto the framework's
     parameter pytree, and run distributed inference/MD.

Run: python examples/04_pretrained_and_uma.py [path/to/mace.npz]
Without an exported checkpoint this demo falls back to a synthetic
state dict with upstream names/shapes, which exercises the exact same
conversion path.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# Runs on the backend jax finds. For the 8-virtual-device CPU mesh:
#   JAX_PLATFORMS=cpu python examples/04_pretrained_and_uma.py
jax.config.update("jax_num_cpu_devices", 8)  # read by the CPU backend only

import numpy as np

from distmlip_tpu import geometry
from distmlip_tpu.calculators import Atoms, DistPotential, UMAPredictor
from distmlip_tpu.models import ESCN, ESCNConfig, MACE, MACEConfig
from distmlip_tpu.models.convert import from_torch

# --- 1. a MACE model shaped like the checkpoint ---------------------------
# For a real MACE-MP-0-medium export use: num_species=89, channels=128,
# l_max=3, a_lmax=3, hidden_lmax=1, correlation=3, cutoff=6.0, cutoff_p=5.
cfg = MACEConfig(
    num_species=8, channels=16, l_max=3, a_lmax=2, hidden_lmax=1,
    correlation=3, num_interactions=2, num_bessel=8, radial_mlp=16,
    cutoff=5.0, avg_num_neighbors=14.0,
)
model = MACE(cfg)
params = jax.device_get(model.init(jax.random.PRNGKey(0)))

if len(sys.argv) > 1:
    sd = dict(np.load(sys.argv[1]))
else:
    sys.path.insert(0, ".")
    from tests.test_convert import synthetic_mace_state_dict

    sd = synthetic_mace_state_dict(model, np.random.default_rng(0))
    print("(no export given: using a synthetic upstream-shaped state dict)")

params, report = from_torch("mace", sd, params, model=model)
print(f"converted {report['mapped']} tensors, {len(report['unused_torch'])} unmapped")

# --- 2. distributed inference with the converted weights ------------------
rng = np.random.default_rng(1)
unit = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]])
frac, lattice = geometry.make_supercell(unit, np.eye(3) * 5.4, (9, 3, 3))
cart = geometry.frac_to_cart(frac, lattice) + rng.normal(0, 0.05, (len(frac), 3))
atoms = Atoms(numbers=rng.integers(1, 9, len(cart)), positions=cart, cell=lattice)
smap = np.arange(-1, 9, dtype=np.int32)

pot = DistPotential(model, params, num_partitions=4, species_map=smap,
                    skin=0.5)
res = pot.calculate(atoms)
print(f"MACE (converted, 4-way): E = {res['energy']:.4f} eV, "
      f"|F|max = {np.abs(res['forces']).max():.4f} eV/Å")

# --- 3. UMA checkpoint ingestion (fairchem eSCNMD parameterization) -------
# The reference's flagship flow (uma_example.ipynb: from_existing around a
# pretrained eSCNMDBackbone). ESCNMD mirrors that backbone tensor-for-tensor,
# so a fairchem-named state dict converts with zero unmapped tensors; here a
# synthetic UMA-shaped dict stands in (zero-egress image — where fairchem IS
# installed, run the one-command check instead:
#   python -m distmlip_tpu.tools.verify_upstream escn uma.pt
# which exports, infers the config, converts, and compares E/F upstream).
from distmlip_tpu.models import ESCNMD

# the synthetic UMA-shaped dict lives beside the golden oracle and needs
# torch; with torch absent (or no repo checkout) this section is skipped
# and the torch-free MACE/eSCN paths above still run
try:
    from tests.test_convert_escn import CFG as UMA_CFG
    from tests.test_convert_escn import synthetic_escn_state_dict
except ImportError as e:
    print(f"(skipping eSCN/UMA conversion demo: {e})")
else:
    uma_sd = synthetic_escn_state_dict()
    uma_model = ESCNMD(UMA_CFG)
    uma_params = jax.device_get(uma_model.init(jax.random.PRNGKey(1)))
    uma_params, rep = from_torch("escn", uma_sd, uma_params, model=uma_model)
    print(f"eSCN/UMA: converted {rep['mapped']} tensors, "
          f"{len(rep['unused_torch'])} unmapped")
    smap5 = np.concatenate([[0], np.arange(0, 5)]).astype(np.int32)
    atoms5 = Atoms(numbers=rng.integers(1, 6, len(cart)), positions=cart,
                   cell=lattice)
    predictor = UMAPredictor(uma_model, uma_params, task_name="omat",
                             num_partitions=4, species_map=smap5)
    atoms5.info.update(charge=1, spin=2)
    res = predictor.calculate(atoms5)
    print(f"UMA (converted eSCNMD, omat task, charge=1, spin=2, 4-way): "
          f"E = {res['energy']:.4f} eV")

# --- 4. UMA-style conditioned inference (native-parameterization eSCN) ----
uma_cfg = ESCNConfig(num_species=8, channels=16, l_max=2, num_layers=2,
                     num_bessel=6, num_experts=4, cutoff=5.0)
uma = ESCN(uma_cfg)
uma_params = uma.init(jax.random.PRNGKey(1))
predictor = UMAPredictor(uma, uma_params, task_name="omat",
                         num_partitions=4, species_map=smap)
atoms.info.update(charge=1, spin=2)
res = predictor.calculate(atoms)
print(f"UMA (omat task, charge=1, spin=2, 4-way): E = {res['energy']:.4f} eV")
