"""Model zoo registry: single-task graphs + the multi-task head wrapper.

``get_model`` is the one dispatch point: a config with more than one task
(``--tasks ctr,cvr``) builds the multi-task model (``--multitask``
architecture over the shared graph bottom); otherwise ``cfg.model`` picks a
single-task graph from the registry, or one of the decoders (``sdar_moe``,
``kimi_linear``, ``solar_open2``, ``lfm2_moe``, ``phi4_flash``,
``glm4_moe_lite``, ``afmoe``), which are no rankers.
"""

from typing import Union

from ..config import Config
from .afmoe import Afmoe
from .graph import DLRM, GraphDLRMDCNv2
from .graph import GraphDCNv2 as DCNv2
from .graph import GraphDeepFM as DeepFM
from .graph import GraphWideDeep as WideDeep
from .glm4_moe_lite import Glm4MoeLite
from .kimi_linear import KimiLinear
from .lfm2_moe import Lfm2Moe
from .multitask import MultiTaskModel  # noqa: F401
from .phi4_flash import Phi4Flash
from .sdar_moe import SdarMoE
from .sequence import GraphBST, GraphDIN  # noqa: F401
from .solar_open2 import SolarOpen2

_REGISTRY = {
    "deepfm": DeepFM,
    "widedeep": WideDeep,
    "dcnv2": DCNv2,
    "dlrm": DLRM,
    "dlrm_dcnv2": GraphDLRMDCNv2,
    "din": GraphDIN,
    "bst": GraphBST,
    "sdar_moe": SdarMoE,
    "kimi_linear": KimiLinear,
    "solar_open2": SolarOpen2,
    "lfm2_moe": Lfm2Moe,
    "phi4_flash": Phi4Flash,
    "glm4_moe_lite": Glm4MoeLite,
    "afmoe": Afmoe,
}

CtrModel = Union[DeepFM, WideDeep, DCNv2, DLRM, GraphDLRMDCNv2, GraphDIN,
                 GraphBST, SdarMoE, KimiLinear, SolarOpen2, Lfm2Moe,
                 Phi4Flash, Glm4MoeLite, Afmoe, MultiTaskModel]


def registered_models():
    """Registered single-task ranker names: the zoo every ranker test and
    tool walks. A model that owns its loss (``owns_loss``: no one logit an
    example, so no AUC and no servable) is built by ``get_model`` and is not
    of that zoo."""
    return sorted(name for name, cls in _REGISTRY.items()
                  if not getattr(cls, "owns_loss", False))


def get_model(cfg: Config) -> CtrModel:
    if cfg.num_tasks > 1:
        return MultiTaskModel(cfg)
    if cfg.model not in _REGISTRY:     # (not a constructor's own KeyError)
        raise ValueError(f"unknown model {cfg.model!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[cfg.model](cfg)
