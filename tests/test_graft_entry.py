"""Driver-contract test: __graft_entry__.dryrun_multichip must succeed in a
FRESH process on a host with fewer real devices than requested — i.e. it must
self-provision the virtual 8-device CPU mesh (the round-1 failure mode: the
entry asserted on device count instead of provisioning).
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_self_provisions():
    # Strip any device-count overrides the test harness set: the driver's
    # process starts with none of them.
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    # Prepend (not replace): the driver's process may rely on entries
    # already on PYTHONPATH.
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"
    p = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, f"dryrun failed:\n{p.stderr[-3000:]}"
    assert "dryrun_multichip ok" in p.stdout


def test_entry_returns_jittable():
    import jax

    import __graft_entry__

    fn, example_args = __graft_entry__.entry()
    out = jax.jit(fn)(*example_args)
    assert out.shape == (1024,)
    import numpy as np
    probs = np.asarray(out)
    assert np.all(probs >= 0) and np.all(probs <= 1)
