from ..obs import startup

# What every importer of the trainer pays first, the benchmark's driver on a
# thread of its own too: the loop, and under it the models and the kernels.
with startup.importing("deepfm_tpu.train.loop"):
    from . import loop, metrics, optimizers  # noqa: F401
from .loop import Trainer  # noqa: F401,E402
from .state import TrainState  # noqa: F401,E402
