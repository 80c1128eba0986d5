"""Train state pytree: params + optimizer state + model (BN) state + PRNG."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..obs import startup

with startup.importing("flax"):   # half a second on the chip's host
    import flax.struct


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray          # int32 scalar (global_step analog)
    params: Any
    opt_state: Any
    model_state: Any           # BatchNorm running stats etc.
    rng: jax.Array             # base PRNG key; per-step keys are folded in

    @classmethod
    def create(cls, params: Any, opt_state: Any, model_state: Any,
               rng: jax.Array) -> "TrainState":
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, model_state=model_state, rng=rng)
