#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main path once — launcher -> Trainer -> checkpoint/publish/export
-> ServingEngine — through the entry points a user would call, at the full
width of the reference DeepFM (V=117,581, F=39, K=32, tower 128/64/32, batch
1024, every other flag at its default), with seeded random data and weights:

  1. every Pallas kernel in ``deepfm_tpu/ops`` compiled (not interpreted) at
     the reference shape and compared with its XLA leg;
  2. ``deepfm_tpu.launch.main`` train task in online mode: steps, eval,
     checkpoints, hot publishes and the servable export;
  3. the launcher's infer task (``Trainer.predict`` over a test shard) and
     ``ServingEngine.serve_latest`` on the publish directory answering the
     same rows in several buckets, compared within a bf16 tolerance; then a
     second train call that resumes from the checkpoint on one more shard;
  4. a few sparse-update steps (``--embedding_update sparse``, default
     ``--embedding_kernels``);
  5. with >= 4 devices: the train leg on 4x1 and 2x2 meshes and the sparse
     leg row-sharded 1x4, with the per-device shards and memory checked.

One process (a chip belongs to one process at a time), no option that lets
it pass without a TPU, no phase wrapped in try/except: any failure is a
non-zero exit and no result line. On success the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``tests/test_chip_smoke.py`` rehearses these functions at a tiny size on the
CPU backend (Pallas in interpret mode); the script itself has no CPU mode.
"""

from __future__ import annotations

import contextlib
import glob
import io
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Sequence

import numpy as np

#: The reference DeepFM cell, at full width. Everything not listed here —
#: bf16 compute, steps_per_loop=8, native decoder, kernels — stays default.
REFERENCE = {"feature_size": 117581, "field_size": 39, "embedding_size": 32,
             "deep_layers": "128,64,32", "batch_size": 1024}

#: Row counts of the serving requests: buckets 1, 4, 32, 64 and 256.
REQUEST_ROWS = (1, 3, 17, 64, 200)


_T0 = time.time()


def say(msg: str) -> None:
    print(f"chip_smoke [{time.time() - _T0:6.1f}s]: {msg}", flush=True)


def model_flags(shape: Dict[str, object]) -> List[str]:
    return [tok for k, v in shape.items() for tok in (f"--{k}", str(v))]


def run_launcher(argv: Sequence[str]) -> dict:
    """``deepfm_tpu.launch.main(argv)`` — the real launcher — returning the
    result object it prints as its last stdout line."""
    from deepfm_tpu import launch

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = launch.main(list(argv))
    sys.stdout.write(buf.getvalue())
    sys.stdout.flush()
    assert rc == 0, f"launcher exited {rc} for {list(argv)}"
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def make_shards(out_dir: str, shape: Dict[str, object], prefix: str,
                num_files: int, examples_per_file: int, seed: int
                ) -> List[str]:
    from deepfm_tpu.data import libsvm

    return libsvm.generate_synthetic_ctr(
        out_dir, num_files=num_files, examples_per_file=examples_per_file,
        feature_size=int(shape["feature_size"]),
        field_size=int(shape["field_size"]), prefix=prefix, seed=seed)


# ---------------------------------------------------------------------------
# 1. Kernels: compiled Pallas vs the XLA leg
# ---------------------------------------------------------------------------

def check_kernels(shape: Dict[str, object], *, interpret: bool = False
                  ) -> dict:
    """fused_fm and take_rows_pallas, forward and backward, and put_rows
    against their XLA legs at (B, F, K) of ``shape``; the block-masked
    attention kernel against the chunked XLA path, the expert layer's row
    kernels against take and scatter-add and its grouped products against
    ``jax.lax.ragged_dot`` at shapes of their own.
    ``interpret=False`` is the compiled path (TPU only); the CPU rehearsal
    passes True."""
    import jax
    import jax.numpy as jnp

    from deepfm_tpu.ops import pallas_embedding as pemb
    from deepfm_tpu.ops import pallas_fm

    b = int(shape["batch_size"])
    f = int(shape["field_size"])
    k = int(shape["embedding_size"])
    rng = np.random.default_rng(0)
    out = {}

    def max_rel(got, want) -> float:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                      1e-30))

    for name, dtype, tol in (("float32", jnp.float32, 1e-5),
                             ("bfloat16", jnp.bfloat16, 2.0 ** -7)):
        w = jnp.asarray(rng.normal(size=(b, f)), dtype)
        vals = jnp.asarray(rng.normal(size=(b, f)), dtype)
        xv = jnp.asarray(rng.normal(size=(b, f, k)) * 0.1, dtype)
        fwd = jax.jit(lambda *a: pallas_fm.fused_fm(*a, interpret))
        ref = jax.jit(pallas_fm.reference_fm)
        errs = [max_rel(fwd(w, vals, xv), ref(w, vals, xv))]
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(pallas_fm.fused_fm(*a, interpret) ** 2),
            argnums=(0, 1, 2)))(w, vals, xv)
        want = jax.jit(jax.grad(
            lambda *a: jnp.sum(pallas_fm.reference_fm(*a) ** 2),
            argnums=(0, 1, 2)))(w, vals, xv)
        errs += [max_rel(g, r) for g, r in zip(grads, want)]
        assert max(errs) <= tol, f"fused_fm {name} vs XLA: {errs} > {tol}"
        out[f"fused_fm_{name}_max_rel_err"] = max(errs)

    # take: U = N = B*F gathered rows of width K+1 (every name's columns in
    # one leaf), the largest working set the sparse plane asks of it.
    n, d = b * f, k + 1
    rows = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    inv = jnp.asarray(rng.integers(0, n, size=(b, f)), jnp.int32)
    got = jax.jit(lambda r, i: pemb.take_rows_pallas(r, i, interpret))(
        rows, inv)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.take(rows, inv, axis=0)))
    g = jax.jit(jax.grad(lambda r: jnp.sum(
        pemb.take_rows_pallas(r, inv, interpret) ** 2)))(rows)
    g_ref = jax.jit(jax.grad(lambda r: jnp.sum(
        jnp.take(r, inv, axis=0) ** 2)))(rows)
    err = max_rel(g, g_ref)
    assert err <= 1e-5, f"take_rows_pallas backward vs XLA: {err}"
    out["take_rows_bwd_max_rel_err"] = err

    # put_rows: a table and its twin, distinct rows with a spare tail (ids
    # past the table: skipped, never copied) in one launch, bit for bit
    # XLA's scatter. A row is one 128-lane line whatever the shape's K.
    from deepfm_tpu.ops import pallas_put_rows

    real, spare = n - n // 8, n // 8
    tabs = [jnp.asarray(rng.normal(size=(4 * n, 128)), jnp.float32)
            for _ in range(2)]
    news = [jnp.asarray(rng.normal(size=(n, 128)), jnp.float32)
            for _ in range(2)]
    uids = jnp.asarray(np.concatenate([
        np.sort(rng.choice(4 * n, real, replace=False)),
        4 * n + np.arange(spare)]), jnp.int32)
    got = jax.jit(lambda t, u, r: pallas_put_rows.put_rows_many(
        t, u, r, interpret=interpret))(tabs, uids, news)
    for t, r, g in zip(tabs, news, got):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(t.at[uids].set(r, mode="drop")))
    out["put_rows_slots_written"] = real

    # block-masked attention (the decoders' masked scores): the kernel,
    # forward and both backward kernels, against the chunked XLA path on one
    # key/value head's query heads of 128 over 1,024 positions, bfloat16
    # operands: four heads under SDAR's block-diffusion mask, eight under
    # Solar-Open2's causal one, LFM2's four heads of 64 (half a lane
    # line, as they are) under the causal mask, Phi-4-flash's two heads
    # of 64 on a pair's values of 128 under a window's band (300 positions:
    # the diagonal blocks and part of the one below), and GLM-4.7-Flash's
    # latent attention: one query head a key/value head at keys and values
    # of 256 (two lane lines) under the causal mask.
    from deepfm_tpu.models import kimi_linear, phi4_flash, sdar_moe

    length, cdt = 512, jnp.dtype(jnp.bfloat16)
    errs = []
    for heads, head_dim, value_dim, mask in (
            (4, 128, 128, sdar_moe.block_diffusion(length, 4)),
            (8, 128, 128, kimi_linear.causal),
            (4, 64, 64, kimi_linear.causal),
            (2, 64, 128, phi4_flash.window(300)),
            (1, 256, 256, kimi_linear.causal)):
        q, key, val = (jnp.asarray(rng.normal(size=shape_), jnp.float32)
                       for shape_ in ((1, 2 * length, 1, heads, head_dim),
                                      (1, 2 * length, 1, head_dim),
                                      (1, 2 * length, 1, value_dim)))
        key, val = key.astype(cdt), val.astype(cdt)
        weight = jnp.asarray(
            rng.normal(size=(1, 2 * length, heads * value_dim)), jnp.float32)

        def by_kernel(q, k, v):
            return sdar_moe._scores_kernel(
                (q / np.sqrt(float(head_dim))).astype(cdt), k, v, mask=mask,
                interpret=interpret)

        def by_xla(q, k, v):
            return sdar_moe._scores_xla(q.astype(cdt), k, v, mask=mask,
                                        cdt=cdt)

        def out_and_grads(f):
            def loss(q, k, v):
                o = f(q, k, v).astype(jnp.float32)
                return jnp.sum(o * weight), o
            (_, o), g = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))(q, key, val)
            return (o, *g)

        errs += [max_rel(g, w) for g, w in zip(out_and_grads(by_kernel),
                                               out_and_grads(by_xla))]
    assert max(errs) <= 2.0 ** -5, f"block attention vs XLA: {errs}"
    out["block_attention_max_rel_err"] = max(errs)

    # the expert layer's rows (ops/pallas_moe_rows): a pass of 1,024 buffer
    # rows of 2,304 (18 lines) in 4 groups, 700 of them held, over 512
    # positions, taken (bfloat16) and added back weighted, forward and
    # backward, against take / scatter-add; spare rows name no position.
    from deepfm_tpu.ops import pallas_moe_rows as pmr

    positions, buf, held, width = 512, 1024, 700, 2304
    tok = np.concatenate([np.sort(rng.choice(positions, 256, replace=False))
                          for _ in range(4)]).astype(np.int32)
    ends = jnp.asarray(np.minimum(256 * np.arange(1, 5), held), jnp.int32)
    spare = jnp.asarray(np.where(np.arange(buf) < held, tok, 2 ** 30))
    tok, valid = jnp.asarray(tok), jnp.arange(buf) < held
    x, carry = (jnp.asarray(rng.normal(size=(positions, width)), jnp.float32)
                for _ in range(2))
    y = jnp.asarray(rng.normal(size=(buf, width)), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.1, 1.0, buf), jnp.float32)

    def rows_by_kernel(x, y, carry, scale):
        # (x's gradient comes through the argument that rides along)
        xs, _ = pmr.gather(jax.lax.stop_gradient(x), x, spare, ends,
                           jnp.bfloat16, interpret=interpret)
        return pmr.combine(carry, xs.astype(jnp.float32) * y, scale, spare,
                           ends, interpret=interpret)

    def rows_by_xla(x, y, carry, scale):
        xs = jnp.where(valid[:, None], jnp.take(x, tok, axis=0),
                       0.0).astype(jnp.bfloat16)
        return carry.at[tok].add(jnp.where(
            valid[:, None], xs.astype(jnp.float32) * y * scale[:, None], 0.0))

    def rows_and_grads(f):
        def loss(*a):
            o = f(*a)
            return jnp.sum(o * jnp.cos(carry)), o
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True))(x, y, carry, scale)
        return (o, *g)

    errs = [max_rel(g, w) for g, w in zip(rows_and_grads(rows_by_kernel),
                                          rows_and_grads(rows_by_xla))]
    # (the sum, then the gradients of x, y, carry, scale; x's comes through
    # the bfloat16 rows' cotangent, whose rounding XLA is free to skip on
    # its own path: xla_allow_excess_precision)
    limits = (1e-5, 2.0 ** -7, 1e-5, 1e-5, 1e-5)
    assert all(e <= lim for e, lim in zip(errs, limits)), (
        f"moe rows vs XLA: {errs} > {limits}")
    out["moe_rows_max_rel_err"] = max(errs)

    # the expert layer's grouped products (ops/pallas_grouped_dot): a pass
    # of the SDAR cell's shape (16,384 rows of 2,048 by 16 experts of 768;
    # the rehearsal's interpreter a small one), bfloat16 operands, half full
    # and empty, the rows and both gradients against ``jax.lax.ragged_dot``
    # over the whole buffer with the spare rows (zeros) in the last group.
    # The kernels leave the rows past the prefix as they found them: they
    # are compared on the prefix.
    from deepfm_tpu.ops import pallas_grouped_dot as pgd

    buf, width, hidden, groups = ((512, 256, 128, 4) if interpret
                                  else (16384, 2048, 768, 16))
    a = jnp.asarray(rng.normal(size=(buf, width)), jnp.bfloat16)
    mats = jnp.asarray(rng.normal(size=(groups, width, hidden)) * 0.03,
                       jnp.bfloat16)
    # (a cotangent bfloat16 holds: the kernels round a float32 one to it,
    # as the MXU does for XLA's)
    dy = jnp.asarray(rng.normal(size=(buf, hidden)), jnp.bfloat16).astype(
        jnp.float32)
    errs = []
    for held in (buf // 2 - 37, 0):
        share = rng.uniform(0.7, 1.3, groups)
        ends = np.round(np.cumsum(share / share.sum()) * held).astype(
            np.int32)
        ends[-1] = held
        ends, valid = jnp.asarray(ends), (jnp.arange(buf) < held)[:, None]
        a_held = jnp.where(valid, a, 0).astype(jnp.bfloat16)

        def by_kernel(a, w):
            return pgd.grouped_dot(a, w, ends, interpret=interpret)

        def by_ragged_dot(a, w):
            sizes = jnp.diff(ends, prepend=0)
            return jax.lax.ragged_dot(
                a, w, sizes.at[-1].add(buf - held),
                preferred_element_type=jnp.float32)

        def products_and_grads(f):
            def loss(a, w):
                o = f(a, w)
                return jnp.sum(jnp.where(valid, o * dy, 0.0)), o
            (_, o), (da, dw) = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True))(a_held, mats)
            return jnp.where(valid, o, 0.0), jnp.where(valid, da, 0), dw

        got, want = (products_and_grads(f)
                     for f in (by_kernel, by_ragged_dot))
        assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in got)
        errs += [max_rel(g, w) for g, w in zip(got, want)]
    # (the sums differ in their order; the gradients leave in bfloat16)
    assert max(errs) <= 2.0 ** -7, f"grouped products vs ragged_dot: {errs}"
    out["moe_grouped_dot_max_rel_err"] = max(errs)

    # the selective scan as the program ships it here
    # (models/phi4_flash.selective_scan by ``scan_by``'s word: on a TPU the
    # two kernels of ops/pallas_selective_scan, their state in registers;
    # the rehearsal runs them through the interpreter) against a
    # ``lax.scan`` over positions: 2,048 positions (32 time blocks) of 1,024
    # channels x 16 states, float32, the output and every input's gradient.
    t_len, width, states = 2048, 1024, 16
    by = phi4_flash.scan_by(width, t_len,
                            backend="tpu" if interpret else None)
    assert by == "kernel", by
    xs = jnp.asarray(rng.normal(size=(1, t_len, width)), jnp.float32)
    step = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                          (1, t_len, width))), jnp.float32)
    rate = -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=jnp.float32),
                             (width, states))
    b_in, c_out = (jnp.asarray(rng.normal(size=(1, t_len, states)),
                               jnp.float32) for _ in range(2))
    skip = jnp.asarray(rng.normal(size=(width,)), jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(1, t_len, width)), jnp.float32)

    def by_positions(x, d, a, b_, c_, skip_):
        def one(state, at):             # state [N, C]
            x_t, d_t, b_t, c_t = at
            state = jnp.exp(d_t[None, :] * a.T) * state \
                + (d_t * x_t)[None, :] * b_t[:, None]
            return state, jnp.sum(c_t[:, None] * state, axis=0)
        _, y = jax.lax.scan(one, jnp.zeros((states, width), jnp.float32),
                            (x[0], d[0], b_[0], c_[0]))
        return y[None] + skip_ * x

    def scan_and_grads(f):
        def loss(*a):
            y = f(*a)
            return jnp.sum(y * w_out), y
        (_, y), g = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(6)), has_aux=True))(
                xs, step, rate, b_in, c_out, skip)
        return (y, *g)

    errs = [max_rel(g, w) for g, w in zip(
        scan_and_grads(lambda *a: phi4_flash.selective_scan(
            *a, by=by, interpret=interpret)[0]),
        scan_and_grads(by_positions))]
    assert max(errs) <= 1e-4, f"selective scan vs positions: {errs}"
    out["selective_scan_max_rel_err"] = max(errs)

    # the delta-rule scan as the program ships it here
    # (models/kimi_linear.kda_scan by ``kda_scan_by``'s word: on a TPU the two
    # kernels of ops/pallas_kda_scan; the rehearsal runs them through the
    # interpreter) against XLA's chunked form: 512 positions (8 chunks) of
    # 2 heads x 128, the write strength to 2, a log-decay to -8 a position
    # (-64 a sub-chunk of 16 in the mean and past it on many channels: the
    # kernels take every pair's exponent as a sum of log-decays, as XLA's
    # form does pair by pair); the output and every input's gradient.
    # Float32 throughout:
    # the products with the state too, which at the default precision are
    # one bfloat16 pass on a TPU in either form (the two then differ by
    # 7e-4 of the output's size; chip, PR 45).
    length, heads, dim = 512, 2, 128
    by = kimi_linear.kda_scan_by(length, dim,
                                 backend="tpu" if interpret else None)
    assert by == "kernel", by

    def unit(y):
        return y / np.linalg.norm(y, axis=-1, keepdims=True)

    shape = (1, length, heads, dim)
    scan_in = tuple(jnp.asarray(x, jnp.float32) for x in (
        unit(rng.normal(size=shape)) * dim ** -0.5,
        unit(rng.normal(size=shape)), rng.normal(size=shape),
        -rng.uniform(0.001, 8.0, shape), rng.uniform(0.0, 2.0, shape[:3])))
    w_o = jnp.asarray(rng.normal(size=shape), jnp.float32)

    def kda_and_grads(**how):
        def loss(*a):
            o, _ = kimi_linear.kda_scan(*a, cdt=jnp.dtype("float32"), **how)
            return jnp.sum(o * w_o), o
        with jax.default_matmul_precision("highest"):
            (_, o), g = jax.jit(jax.value_and_grad(
                loss, argnums=tuple(range(5)), has_aux=True))(*scan_in)
        return (o, *g)

    errs = [max_rel(g, w) for g, w in zip(
        kda_and_grads(by=by, interpret=interpret), kda_and_grads())]
    assert max(errs) <= 1e-4, f"delta-rule scan vs XLA: {errs}"
    out["kda_scan_max_rel_err"] = max(errs)
    return out


# ---------------------------------------------------------------------------
# 2. Launcher: train + eval + checkpoint + publish + export; resume
# ---------------------------------------------------------------------------

def train_flags(work: str, shape: Dict[str, object],
                idle_secs: float = 2.0) -> List[str]:
    """Online-mode train task: the one launcher call that evals, checkpoints,
    publishes and exports. Only deployment settings are given — directories,
    cadences sized to a few tens of steps, and how long a drained stream
    waits for more shards before the run ends."""
    return model_flags(shape) + [
        "--task_type", "train",
        "--data_dir", os.path.join(work, "stream"),
        "--val_data_dir", os.path.join(work, "eval"),
        "--model_dir", os.path.join(work, "ckpt"),
        "--servable_model_dir", os.path.join(work, "servable"),
        "--publish_dir", os.path.join(work, "publish"),
        "--pipe_mode", "1", "--online_mode", "true",
        "--publish_every_steps", "16", "--save_checkpoints_steps", "16",
        "--stream_poll_secs", "0.2",
        "--stream_idle_timeout_secs", str(idle_secs),
    ]


def check_train(work: str, shape: Dict[str, object], *, steps: int,
                min_auc: float = 0.5, idle_secs: float = 2.0) -> dict:
    """``steps`` optimizer steps from scratch through the launcher, with
    eval, checkpoints, publishes and the servable export. Eval AUC must
    exceed ``min_auc`` (the tiny CPU rehearsal has too few examples to learn
    from and lowers it)."""
    from deepfm_tpu.utils import export as export_lib

    bs = int(shape["batch_size"])
    make_shards(os.path.join(work, "stream"), shape, "tr", steps // 8,
                8 * bs, seed=1)
    make_shards(os.path.join(work, "eval"), shape, "va", 1, 8 * bs, seed=2)
    res = run_launcher(train_flags(work, shape, idle_secs))
    assert res["steps"] == steps, res
    assert np.isfinite(res["loss"]), res
    assert res["auc"] > min_auc, f"eval AUC {res['auc']} after {steps} steps"
    assert res["saved_model"], res

    latest = export_lib.read_latest(os.path.join(work, "publish"))
    assert latest is not None and os.path.basename(latest) == str(steps), (
        f"LATEST -> {latest}")
    check_artifact(latest)
    check_artifact(os.path.join(work, "servable", str(steps)))
    return res


def check_artifact(artifact: str) -> None:
    from deepfm_tpu.utils import export as export_lib

    for name in ("serving_fn.stablehlo", export_lib.COMPLETE_MARKER):
        assert os.path.exists(os.path.join(artifact, name)), (
            f"{artifact} has no {name}")


def check_resume(work: str, shape: Dict[str, object], *, trained: int,
                 more: int) -> dict:
    """A second launcher call on the same ``--model_dir``: a default
    (file-mode) train task over one new shard must restore the first call's
    checkpoint at step ``trained`` and take ``more`` steps from there."""
    data = os.path.join(work, "more")
    make_shards(data, shape, "tr", 1, more * int(shape["batch_size"]), seed=3)
    res = run_launcher(model_flags(shape) + [
        "--task_type", "train", "--data_dir", data,
        "--val_data_dir", os.path.join(work, "eval"),
        "--model_dir", os.path.join(work, "ckpt"),
        "--servable_model_dir", os.path.join(work, "servable")])
    assert res["steps"] == trained + more, (
        "the second launcher call did not resume from the checkpoint: "
        f"{res['steps']} steps, expected {trained + more}")
    assert np.isfinite(res["loss"]) and np.isfinite(res["eval_loss"]), res
    check_artifact(os.path.join(work, "servable", str(trained + more)))
    return res


# ---------------------------------------------------------------------------
# 3. Serving: the engine's answers vs the trainer's predict
# ---------------------------------------------------------------------------

def read_rows(path: str, field_size: int):
    """(ids [n, F] int32, vals [n, F] float32) of a shard, in file order."""
    from deepfm_tpu.data import example_codec, tfrecord

    ids, vals = [], []
    for rec in tfrecord.iter_records(path):
        _, rid, rval = example_codec.decode_ctr_example(rec, field_size)
        ids.append(rid.astype(np.int32))
        vals.append(np.asarray(rval, np.float32))
    return np.stack(ids), np.stack(vals)


def check_serving(work: str, shape: Dict[str, object], *,
                  request_rows: Sequence[int] = REQUEST_ROWS,
                  atol: float = 2e-2) -> dict:
    """The launcher's infer task writes ``Trainer.predict`` for a test shard;
    ``ServingEngine.serve_latest`` then answers the same rows as requests of
    ``request_rows`` rows each, and the two must agree within ``atol`` (the
    server runs the exported program, the trainer its own predict step)."""
    from deepfm_tpu.serve.engine import ServingEngine
    from deepfm_tpu.utils import export as export_lib

    test_dir = os.path.join(work, "test")
    shard = make_shards(test_dir, shape, "te", 1, sum(request_rows),
                        seed=4)[0]
    infer = run_launcher(model_flags(shape) + [
        "--task_type", "infer", "--val_data_dir", test_dir,
        "--model_dir", os.path.join(work, "ckpt")])
    assert infer["num_predictions"] == sum(request_rows), infer
    with open(os.path.join(test_dir, "pred.txt")) as f:
        want = np.asarray([float(line) for line in f], np.float32)
    assert np.all(np.isfinite(want)) and want.min() >= 0 and want.max() <= 1

    ids, vals = read_rows(shard, int(shape["field_size"]))
    engine = ServingEngine.serve_latest(os.path.join(work, "publish"))
    try:
        assert engine.watcher.prewarmed_buckets == len(engine.buckets), (
            engine.watcher.prewarmed_buckets, engine.buckets)
        buckets, worst, lo = [], 0.0, 0
        for n in request_rows:
            got = engine.submit(ids[lo:lo + n], vals[lo:lo + n]).result(
                timeout=300)
            assert got.shape == (n,) and np.all(np.isfinite(got)), got
            worst = max(worst, float(np.max(np.abs(got - want[lo:lo + n]))))
            buckets.append(export_lib.next_bucket(n, engine.buckets))
            lo += n
        stats = engine.stats.summary()
    finally:
        engine.close()
    assert worst <= atol, (
        f"server and Trainer.predict differ by {worst} > {atol}")
    assert stats["serving_failed"] == 0, stats
    assert stats["serving_requests"] == len(request_rows), stats
    assert len(set(buckets)) >= 2, buckets
    # One flush per request, each padded to its own bucket.
    assert engine.stats.padded_rows == sum(buckets), (
        engine.stats.padded_rows, buckets)
    return {"requests": len(request_rows), "buckets": sorted(set(buckets)),
            "max_abs_diff_vs_trainer": worst,
            "prewarmed_buckets": engine.watcher.prewarmed_buckets}


# ---------------------------------------------------------------------------
# 4/5. File-mode legs: sparse updates, meshes
# ---------------------------------------------------------------------------

def check_file_mode_leg(work: str, shape: Dict[str, object], name: str,
                        extra: Sequence[str], *, steps: int = 16) -> dict:
    """One default (file-mode) train task of ``steps`` steps with eval,
    through the launcher, with ``extra`` flags; loss finite, AUC a number."""
    bs = int(shape["batch_size"])
    data = os.path.join(work, "filemode")
    if not os.path.isdir(data):
        make_shards(data, shape, "tr", steps // 8, 8 * bs, seed=5)
        make_shards(data, shape, "va", 1, 4 * bs, seed=6)
    res = run_launcher(model_flags(shape) + [
        "--task_type", "train", "--data_dir", data, "--val_data_dir", data,
        *extra])
    assert res["steps"] == steps, (name, res)
    assert np.isfinite(res["loss"]) and np.isfinite(res["eval_loss"]), (
        name, res)
    assert 0.0 <= res["auc"] <= 1.0, (name, res)
    return res


def check_step_uses_pallas(shape: Dict[str, object], expect: bool) -> int:
    """Lower the trainer's scanned train step — the same config the launcher
    ran — and count the Mosaic kernels in it: the FM block must be the
    compiled Pallas kernel on a TPU, forward and backward."""
    from deepfm_tpu.config import parse_args
    from deepfm_tpu.train import Trainer
    from deepfm_tpu.train.loop import zero_batch

    cfg = parse_args(model_flags(shape))
    trainer = Trainer(cfg)
    batch = zero_batch(cfg.field_size, cfg.batch_size)
    text = trainer.multi_step.lower(
        trainer.init_state(),
        trainer.put_superbatch([batch] * cfg.steps_per_loop)).as_text()
    n = text.count("tpu_custom_call")
    assert (n >= 2) == expect, (
        f"{n} Mosaic kernels in the train step, expected "
        f"{'fused_fm forward and backward' if expect else 'none'}")
    return n


def check_spread(shape: Dict[str, object], extra: Sequence[str],
                 mesh_data: int, mesh_model: int) -> dict:
    """Build the trainer the launcher built for ``extra`` and read where its
    arrays live: batch rows must be split over ``mesh_data`` devices and
    embedding rows over ``mesh_model``, on ``mesh_data * mesh_model``
    DISTINCT devices — not all on device 0."""
    from deepfm_tpu.config import parse_args
    from deepfm_tpu.train import Trainer
    from deepfm_tpu.train.loop import zero_batch

    cfg = parse_args(model_flags(shape) + list(extra))
    trainer = Trainer(cfg)
    table = trainer.init_state().params["fm_v"]
    ids = trainer.put_batch(
        zero_batch(cfg.field_size, cfg.batch_size))["feat_ids"]

    def layout(arr):
        return {s.device.id: tuple(s.data.shape)
                for s in arr.addressable_shards}

    tab, bat = layout(table), layout(ids)
    n = mesh_data * mesh_model
    assert len(tab) == n and len(bat) == n, (tab, bat)
    assert set(bat.values()) == {
        (cfg.batch_size // mesh_data, cfg.field_size)}, bat
    assert set(tab.values()) == {
        (table.shape[0] // mesh_model, cfg.embedding_size)}, tab
    # Distinct row blocks per model-axis peer, distinct batch blocks per
    # data-axis peer: count the different index windows.
    row_blocks = {s.index[0].start or 0 for s in table.addressable_shards}
    batch_blocks = {s.index[0].start or 0 for s in ids.addressable_shards}
    assert len(row_blocks) == mesh_model, row_blocks
    assert len(batch_blocks) == mesh_data, batch_blocks
    return {"devices": sorted(tab), "table_shard": next(iter(tab.values())),
            "batch_shard": next(iter(bat.values()))}


def check_device_memory(min_peak_bytes: int) -> Dict[int, int]:
    """Every device's own ``memory_stats()`` peak: work that all landed on
    device 0 would leave the others' peaks at nothing."""
    import jax

    peaks = {d.id: int(d.memory_stats()["peak_bytes_in_use"])
             for d in jax.devices()}
    assert all(p >= min_peak_bytes for p in peaks.values()), (
        f"per-device peak bytes {peaks}: some device held less than "
        f"{min_peak_bytes}")
    return peaks


def check_multi_device(work: str, shape: Dict[str, object]) -> dict:
    """Four devices: dense train legs on 4x1 and 2x2, the sparse leg with
    rows sharded 1x4 — each through the launcher, each with its layout."""
    out = {}
    for name, extra, md, mm in (
            ("mesh_4x1", ["--mesh_data", "4", "--mesh_model", "1"], 4, 1),
            ("mesh_2x2", ["--mesh_data", "2", "--mesh_model", "2"], 2, 2),
            ("sparse_rows_1x4",
             ["--embedding_update", "sparse", "--embedding_shard", "rows",
              "--mesh_data", "1", "--mesh_model", "4"], 1, 4)):
        res = check_file_mode_leg(work, shape, name, extra)
        out[name] = {"loss": res["loss"], "auc": res["auc"],
                     **check_spread(shape, extra, md, mm)}
        say(f"{name}: {json.dumps(out[name])}")
    return out


# ---------------------------------------------------------------------------

def watch_compiles() -> Dict[str, float]:
    """Running totals of this process's backend compiles — programs, seconds
    spent compiling or fetching them from the persistent cache, and cache
    hits — from JAX's own monitoring events. Compilation is set-up, and a
    warm second run must spend less of it than a cold first one."""
    import jax

    totals = {"programs": 0, "seconds": 0.0, "cache_hits": 0}

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            totals["programs"] += 1
            totals["seconds"] += secs

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return totals


def native_decoder_in_use() -> str:
    """The decoder library this checkout built — on first use, from a tree
    that carries no binary — and that the pipelines therefore decoded with
    (``use_native_decoder`` is on by default and has no Python fallback)."""
    from deepfm_tpu.config import Config
    from deepfm_tpu.native import loader

    assert Config().use_native_decoder
    built = glob.glob(os.path.join(os.path.dirname(loader.__file__),
                                   "_build", "libtfrecord-*.so"))
    assert loader.available() and built, "native decoder was never built"
    return os.path.basename(built[0])


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__} platform={device['platform']} "
        f"device_kind={device['kind']!r} devices={device['count']}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device['platform']!r} ({device['kind']}). Nothing was run.",
              file=sys.stderr)
        return 2

    from deepfm_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    cached_before = compile_cache.entry_count(cache_dir)
    say(f"compile cache {cache_dir}: {cached_before} programs")
    compiles = watch_compiles()

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    shape = dict(REFERENCE)
    say(f"kernels: {json.dumps(check_kernels(shape))}")
    trained = check_train(work, shape, steps=32)
    say(f"savedmodel sidecar on this host: {trained['saved_model']}")
    say(f"serving: {json.dumps(check_serving(work, shape))}")
    check_resume(work, shape, trained=32, more=8)
    say("resumed from the checkpoint: 32 -> 40 steps")
    say(f"train step Mosaic kernels: {check_step_uses_pallas(shape, True)}")
    sparse = check_file_mode_leg(
        work, shape, "sparse", ["--embedding_update", "sparse"])
    say(f"sparse leg: loss={sparse['loss']:.5f} auc={sparse['auc']:.4f}")
    say(f"native decoder: {native_decoder_in_use()}")
    if device["count"] >= 4:
        check_multi_device(work, shape)
        say(f"per-device peak bytes: {check_device_memory(4 << 20)}")
    shutil.rmtree(work)

    cached_after = compile_cache.entry_count(cache_dir)
    assert cached_after > 0, f"no compiled program was cached in {cache_dir}"
    say(f"compile cache {cache_dir}: {cached_before} -> {cached_after} "
        f"programs; compiled {compiles['programs']} programs in "
        f"{compiles['seconds']:.1f}s ({compiles['cache_hits']} cache hits); "
        f"wall {time.time() - _T0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
