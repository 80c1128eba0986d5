#!/usr/bin/env bash
# Runnable L5 launcher: create (or reuse) a TPU slice and run a deepfm_tpu
# task across all its hosts — the TPU-native analog of the SageMaker
# launcher notebooks (reference 1-ps-cpu/deepfm-sagemaker-ps-cpu.ipynb:71-143:
# pick instances, spot, distribution, channels, then estimator.fit).
#
# Usage:
#   scripts/launch_slice.sh \
#     --tpu-name deepfm-v5e --zone us-west4-a --accel-type v5litepod-8 \
#     [--create] [--spot] [--repo-tar] \
#     -- --task_type train --data_dir gs://bucket/criteo --model_dir gs://bucket/ckpt \
#        --feature_size 117581 --field_size 39 --batch_size 1024 --num_epochs 10
#
# Everything after `--` is passed to the per-host entry point verbatim.
#
# What it does:
#   1. (--create) gcloud creates the slice — queued-resources with --spot
#      gives the reference's spot-instance economics (preemption tolerance =
#      checkpoint resume, same as the reference's SageMaker spot story).
#   2. Ships the repo to every host (--repo-tar) or assumes a shared image.
#   3. Runs the task on ALL hosts simultaneously via
#      `gcloud ... tpu-vm ssh --worker=all`: one process per host,
#      `python -m deepfm_tpu.launch --dist_mode 2` (jax.distributed discovers
#      the slice topology itself; each process drives all of its host's
#      chips).
#
# What has actually been run: NOT this script. PR 21 ran the per-host
# command it issues — one launcher process over all the chips of one host —
# on a single four-chip v5e host (4x1, 2x2 and rows-sharded 1x4 meshes,
# PERF.md). The gcloud steps and every multi-host rendezvous are untested on
# the current stack. Several workers per host (`--worker-per-host N`, via
# deepfm_tpu.fanout) was tried on that host and cannot work: the workers
# collide on libtpu's multi-process lock; the option is refused.
set -euo pipefail

TPU_NAME=""
ZONE=""
ACCEL_TYPE="v5litepod-8"
VERSION="tpu-ubuntu2204-base"
CREATE=0
SPOT=0
WORKER_PER_HOST=1
SHIP_REPO=0

while [[ $# -gt 0 ]]; do
  case "$1" in
    --tpu-name) TPU_NAME="$2"; shift 2 ;;
    --zone) ZONE="$2"; shift 2 ;;
    --accel-type) ACCEL_TYPE="$2"; shift 2 ;;
    --version) VERSION="$2"; shift 2 ;;
    --create) CREATE=1; shift ;;
    --spot) SPOT=1; shift ;;
    --worker-per-host) WORKER_PER_HOST="$2"; shift 2 ;;
    --repo-tar) SHIP_REPO=1; shift ;;
    --) shift; break ;;
    *) echo "unknown flag: $1" >&2; exit 2 ;;
  esac
done
TASK_ARGS=("$@")

if [[ "$WORKER_PER_HOST" != 1 ]]; then
  echo "--worker-per-host $WORKER_PER_HOST: several workers per TPU host" \
       "cannot form one device topology (tried on four v5e chips, PERF.md);" \
       "one process per host drives all of its chips" >&2
  exit 2
fi

[[ -n "$TPU_NAME" && -n "$ZONE" ]] || {
  echo "required: --tpu-name and --zone" >&2; exit 2; }

GC=(gcloud compute tpus tpu-vm)

if [[ "$CREATE" == 1 ]]; then
  echo ">> creating TPU slice $TPU_NAME ($ACCEL_TYPE) in $ZONE"
  CREATE_FLAGS=(--zone "$ZONE" --accelerator-type "$ACCEL_TYPE"
                --version "$VERSION")
  [[ "$SPOT" == 1 ]] && CREATE_FLAGS+=(--spot)
  "${GC[@]}" create "$TPU_NAME" "${CREATE_FLAGS[@]}"
fi

# Host topology from the slice description.
NUM_HOSTS=$("${GC[@]}" describe "$TPU_NAME" --zone "$ZONE" \
              --format='value(networkEndpoints.length())')
HOST0_IP=$("${GC[@]}" describe "$TPU_NAME" --zone "$ZONE" \
             --format='value(networkEndpoints[0].ipAddress)')
echo ">> slice $TPU_NAME: $NUM_HOSTS host(s), host0=$HOST0_IP"

if [[ "$SHIP_REPO" == 1 ]]; then
  echo ">> shipping repo to all hosts"
  REPO_ROOT=$(cd "$(dirname "$0")/.." && pwd)
  TAR=/tmp/deepfm_tpu_ship.tgz
  tar -czf "$TAR" -C "$REPO_ROOT" --exclude .git --exclude '__pycache__' .
  "${GC[@]}" scp "$TAR" "$TPU_NAME":/tmp/ --zone "$ZONE" --worker=all
  "${GC[@]}" ssh "$TPU_NAME" --zone "$ZONE" --worker=all \
    --command="mkdir -p ~/deepfm_tpu_run && tar -xzf /tmp/deepfm_tpu_ship.tgz -C ~/deepfm_tpu_run"
fi

QUOTED_ARGS=$(printf ' %q' "${TASK_ARGS[@]}")

# One process per host: jax.distributed discovers the slice topology.
REMOTE_CMD="cd ~/deepfm_tpu_run 2>/dev/null || true; \
python -m deepfm_tpu.launch --dist_mode 2 --worker_per_host 1$QUOTED_ARGS"
echo ">> running on all hosts: $REMOTE_CMD"
"${GC[@]}" ssh "$TPU_NAME" --zone "$ZONE" --worker=all \
  --command="$REMOTE_CMD"
