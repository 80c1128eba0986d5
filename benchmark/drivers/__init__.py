"""Traffic drivers: one module per kind of run (``train``, ``serve``)."""
