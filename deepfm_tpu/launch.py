"""CLI launcher: ``python -m deepfm_tpu.launch --task_type train ...``

The L5/L3 entry point replacing the SageMaker notebook + ``tf.app.run()``
pair (reference ``1-ps-cpu/...py:469-471``). All reference hyperparameters
are accepted as ``--flag value`` argv (the SageMaker hyperparameter-dict
contract); SageMaker-style env defaults (``SM_CHANNELS`` etc.) are honored
by ``parse_args``. See ``examples/launch_tpu.md`` for slice-creation recipes.
"""

from __future__ import annotations

import json
import sys

from .obs import startup

# Most of a short run's set-up is the imports below: stamped here, where the
# launcher pays them, so that the start-up line covers its real path.
with startup.importing("jax"):
    import jax

from .config import parse_args  # noqa: E402
from .parallel import bootstrap  # noqa: E402

with startup.importing("deepfm_tpu.train"):   # the loop, the models; tasks
    from .train import tasks

from .utils import compile_cache  # noqa: E402
from .utils import logging as ulog  # noqa: E402
from .utils import preempt as preempt_lib  # noqa: E402


def device_report() -> dict:
    """The devices this process ran on, as JAX reports them. Stamped into
    every result line so a number can never be read under another device's
    name (a parent collecting children's results reads it from theirs)."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    cfg = parse_args(argv)
    # Before the first compile: JAX decides once whether a persistent
    # compilation cache is in use.
    compile_cache.configure()
    # Bootstrap before the first log line: rank-aware logging calls
    # jax.process_index(), which would initialize the XLA backend and break
    # a later jax.distributed.initialize() (it must run first).
    bootstrap.initialize(cfg)
    bootstrap.start_backend()
    ulog.info("config: " + json.dumps(cfg.to_dict(), sort_keys=True))
    try:
        result = tasks.run(cfg)
    except preempt_lib.Preempted as p:
        # Graceful preemption: the checkpoint + resume sidecar are already
        # durable (the train task force-saved before raising). The distinct
        # exit code tells an orchestrator (scripts/supervise.py) "restart
        # me" as opposed to an ordinary crash.
        ulog.warning(f"exiting after preemption: {p}")
        print(json.dumps({"task": cfg.task_type, "device": device_report(),
                          "preempted": True, "step": p.step}))
        return preempt_lib.EXIT_PREEMPTED
    ulog.info(f"task {cfg.task_type} finished: {result}")
    print(json.dumps({"task": cfg.task_type, "device": device_report(),
                      **result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
