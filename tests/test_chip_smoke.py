"""chip_smoke.py: the gate, and a tiny-size CPU rehearsal of its phases.

The script has no CPU mode — run as a program without a TPU it must exit
non-zero and print no result. The ``on-chip-measurement`` guide's rehearsal
(make the command run end to end at a tiny size here before spending chip
time on it) therefore calls the script's functions directly: same launcher
calls, same checks, a toy shape, Pallas through the interpreter.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

TINY = {"feature_size": 300, "field_size": 5, "embedding_size": 4,
        "deep_layers": "8", "batch_size": 16}


@pytest.fixture(autouse=True)
def _no_tf_sidecar(monkeypatch):
    monkeypatch.setenv("DEEPFM_TPU_SKIP_TF_EXPORT", "1")  # ~10 s per export


def test_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr          # names what it found
    assert "platform=cpu" in p.stdout
    last = p.stdout.strip().splitlines()[-1]
    assert not last.startswith("{"), f"printed a result without a chip: {last}"


def test_single_device_phases_rehearsal(tmp_path):
    """launcher train (online: eval + checkpoint + publish + export) ->
    infer vs ServingEngine.serve_latest -> resume -> sparse leg."""
    work = str(tmp_path)
    res = chip_smoke.check_train(work, TINY, steps=32, min_auc=0.0,
                                 idle_secs=1.0)
    assert res["device"]["platform"] == "cpu"
    assert res["published_versions"] == [16, 32]
    serving = chip_smoke.check_serving(work, TINY, request_rows=(1, 3, 9))
    assert serving["buckets"] == [1, 4, 16]
    chip_smoke.check_resume(work, TINY, trained=32, more=8)
    assert chip_smoke.check_step_uses_pallas(TINY, expect=False) == 0
    chip_smoke.check_file_mode_leg(work, TINY, "sparse",
                                   ["--embedding_update", "sparse"])
    assert chip_smoke.native_decoder_in_use().startswith("libtfrecord-")


@pytest.mark.pallas
def test_kernel_phase_rehearsal():
    out = chip_smoke.check_kernels(TINY, interpret=True)
    assert set(out) == {"fused_fm_float32_max_rel_err",
                        "fused_fm_bfloat16_max_rel_err",
                        "take_rows_bwd_max_rel_err",
                        "put_rows_slots_written",
                        "block_attention_max_rel_err",
                        "moe_rows_max_rel_err",
                        "moe_grouped_dot_max_rel_err",
                        "selective_scan_max_rel_err",
                        "kda_scan_max_rel_err"}


@pytest.mark.slow
def test_multi_device_phases_rehearsal(tmp_path):
    """4x1, 2x2 and rows-sharded 1x4 on the virtual mesh: each leg through
    the launcher, each layout read back from addressable_shards."""
    assert len(jax.devices()) >= 4
    out = chip_smoke.check_multi_device(str(tmp_path), TINY)
    assert out["mesh_2x2"]["table_shard"] == (160, 4)
    assert out["sparse_rows_1x4"]["table_shard"] == (80, 4)
    assert out["mesh_4x1"]["batch_shard"] == (4, 5)
    json.dumps(out)
