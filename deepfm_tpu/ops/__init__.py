from ..obs import startup

# Pallas and Mosaic, which every kernel module of this package imports and
# every importer of the package reaches: stamped once, here.
with startup.importing("jax.experimental.pallas"):
    import jax.experimental.pallas  # noqa: F401

from . import embedding, fm  # noqa: F401,E402
