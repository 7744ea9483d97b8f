from .pair import PairPotential, PairConfig
from .tensornet import TensorNet, TensorNetConfig
from .chgnet import CHGNet, CHGNetConfig
from .mace import MACE, MACEConfig
from .escn import ESCN, ESCNConfig
from .escn_md import ESCNMD, ESCNMDConfig
from .nequip import NequIP, NequIPConfig

__all__ = [
    "PairPotential", "PairConfig",
    "TensorNet", "TensorNetConfig",
    "CHGNet", "CHGNetConfig",
    "MACE", "MACEConfig",
    "ESCN", "ESCNConfig",
    "ESCNMD", "ESCNMDConfig",
    "NequIP", "NequIPConfig",
]
