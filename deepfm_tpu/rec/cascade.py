"""Retrieve→rank cascade serving: one artifact, two stages, one hot swap.

Closes the tentpole loop (README "Retrieval→ranking cascade"): a published
artifact dir carries THREE servables —

  * the ranker (``export_serving``'s StableHLO + params, history-aware via
    the packed-column signature),
  * the twin towers (``towers.npz`` + ``towers_config.json``),
  * the candidate index (``index.npz`` + ``index_meta.json``, recall@k
    stamped).

``export_cascade`` writes the retrieval files FIRST and lets
``export_serving`` finish the dir, so the existing ``ARTIFACT_COMPLETE``
marker certifies all three stages at once. :class:`CascadeEngine` serves
them end-to-end: user history → user tower → index top-N → packed ranking
batch through a :class:`~deepfm_tpu.serve.engine.ServingEngine` → top-k.
Hot swap is ATOMIC across stages: one ``LatestWatcher`` loads ranker +
towers + index off to the side as a single :class:`CascadeModel` and swaps
the composite with one assignment — no request ever ranks new candidates
with an old ranker or vice versa.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data import fileio
from ..models.twin_tower import TwinTower
from ..obs import trace as trace_lib
from ..serve.admission import (DEGRADE_RUNGS, VALUE_DEFAULT,
                               AdmissionController, DegradationLadder)
from ..serve.cache import ResultCache, request_fingerprint
from ..serve.engine import ServingEngine
from ..serve.stats import ServingStats
from ..utils import export as export_lib
from .index import CandidateIndex

TOWERS_FILE = "towers.npz"
TOWERS_CONFIG_FILE = "towers_config.json"

#: which feature field holds the candidate item id (the cascade convention
#: shared with ``train_twin_tower``'s positive extraction)
ITEM_SLOT = 0


def _flatten_params(params) -> Tuple[list, object]:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return [np.asarray(x) for x in leaves], treedef


def save_towers(tower_params, cfg: Config, out_dir: str) -> None:
    """``towers.npz`` (leaves in tree-flatten order) + the config needed to
    rebuild the same tree structure at load time."""
    leaves, _ = _flatten_params(tower_params)
    fileio.makedirs(out_dir)
    np.savez_compressed(os.path.join(out_dir, TOWERS_FILE),
                        **{f"p{i}": leaf for i, leaf in enumerate(leaves)})
    with open(os.path.join(out_dir, TOWERS_CONFIG_FILE), "w") as f:
        json.dump({"config": cfg.to_dict()}, f, indent=2)


def load_towers(in_dir: str) -> Tuple[TwinTower, Dict]:
    """(model, params) from :func:`save_towers` output. The param tree is
    rebuilt from the stored config (same treedef as ``init``), so leaf
    order — not leaf names — is the contract."""
    with open(os.path.join(in_dir, TOWERS_CONFIG_FILE)) as f:
        cfg = Config.from_dict(json.load(f)["config"])
    model = TwinTower(cfg)
    template = model.init(jax.random.PRNGKey(0))
    _, treedef = jax.tree_util.tree_flatten(template)
    data = np.load(os.path.join(in_dir, TOWERS_FILE))
    leaves = [data[f"p{i}"] for i in range(len(data.files))]
    return model, jax.tree_util.tree_unflatten(treedef, leaves)


def export_cascade(ranker_model, ranker_state, cfg: Config, out_dir: str, *,
                   tower_params, index: CandidateIndex,
                   index_meta: Optional[Dict] = None) -> str:
    """Write a complete cascade artifact: towers + index, THEN the ranker
    export (which writes ``ARTIFACT_COMPLETE`` last — the marker certifies
    every stage). ``index_meta`` carries measured stamps (recall@k)."""
    fileio.makedirs(out_dir)
    save_towers(tower_params, cfg, out_dir)
    index.save(out_dir, extra_meta=index_meta)
    return export_lib.export_serving(ranker_model, ranker_state, cfg, out_dir)


def cascade_extra_export(cfg: Config, tower_params, index: CandidateIndex, *,
                         index_meta: Optional[Dict] = None
                         ) -> Callable[[str], None]:
    """``Publisher(extra_export=...)`` hook: stamps the frozen retrieval
    stage into every published ranker version (online training republishes
    the ranker continuously; retraining towers/index is a batch job)."""
    def hook(staging_dir: str) -> None:
        save_towers(tower_params, cfg, staging_dir)
        index.save(staging_dir, extra_meta=index_meta)
    return hook


class CannotFuse(TypeError):
    """This artifact's cascade has no single fused program (structural: a
    multitask ranker answers with a dict of heads). The one condition under
    which the engine serves staged instead; a compile or device error in
    the fused program is not this, and propagates."""


class CascadeModel:
    """ONE loaded artifact version: ranker + towers + index, swap-atomic.

    Callable with the engine's ``(feat_ids, feat_vals)`` signature (ranking
    only — packed columns), and carries the retrieval stage alongside so a
    single reference assignment swaps both."""

    def __init__(self, path: str, *, buckets: Sequence[int]):
        self.path = path
        self.rank_fn = export_lib.load_serving(path, buckets=buckets)
        with fileio.open_stream(
                fileio.join(path, "model_config.json"), "r") as f:
            meta = json.load(f)
        self.field_size = int(meta["config"]["field_size"])
        self.hist_len = int(meta.get("history_len", 0))
        self.tower_model, self.tower_params = load_towers(path)
        self.index, self.index_meta = CandidateIndex.load(path)
        self._user_fn = jax.jit(self.tower_model.user_embed)
        # Fused cascade program cache: one jitted program per
        # (batch_bucket, seq_len, retrieve_k, k) — same bounded-compile
        # discipline as BucketedPredict. Living on the MODEL means a hot
        # swap drops every stale program with the old version for free.
        self._fused_cache: Dict[Tuple[int, int, int, int], Callable] = {}
        self.fused_failed = False   # set on first structural fusion error

    # engine-facing predict: delegate, keep prewarm metadata visible
    def __call__(self, feat_ids, feat_vals):
        return self.rank_fn(feat_ids, feat_vals)

    @property
    def buckets(self):
        return getattr(self.rank_fn, "buckets", None)

    @property
    def input_cols(self):
        return getattr(self.rank_fn, "input_cols", None)

    def user_embed(self, hist_ids: np.ndarray,
                   hist_mask: np.ndarray) -> np.ndarray:
        return np.asarray(self._user_fn(
            self.tower_params, hist_ids.astype(np.int32),
            hist_mask.astype(np.float32)))

    # ------------------------------------------------------ fused program
    @property
    def supports_fused(self) -> bool:
        """The fused device program needs a TRACEABLE ranker (the artifact
        loader attaches ``raw_call`` when the StableHLO/params path allows
        it) and a fusable index — ``brute`` is one ``top_k`` over a matmul;
        the ANN's host-side partition scan cannot live inside jit."""
        return (getattr(self.rank_fn, "raw_call", None) is not None
                and self.index.kind == "brute"
                and not self.fused_failed)

    def fused_program(self, batch: int, seq_len: int, retrieve_k: int,
                      k: int) -> Callable:
        """ONE jitted program for the whole per-request cascade at this
        shape: user tower -> device top-k retrieval -> candidate
        substitution into ``ITEM_SLOT`` -> history fitting -> ranker ->
        device top-k of the ranked probabilities. Everything between the
        request arrays and the final (ids, probs) stays on device — no
        host round-trip between stages. Compiled once per shape key and
        cached on this model version.

        Stage-for-stage it computes exactly what the staged path computes:
        the same ``q @ V.T`` + ``lax.top_k`` retrieval (same tie-break:
        lowest index first, matching the staged ``argsort(kind="stable")``),
        the same zero-padded history fit, and the ranker through the same
        exported program — pinned bit-equal in ``tests/test_cascade.py``.
        """
        key = (int(batch), int(seq_len), int(retrieve_k), int(k))
        fn = self._fused_cache.get(key)
        if fn is not None:
            return fn
        raw = self.rank_fn.raw_call
        mat = jnp.asarray(self.index.vectors)                    # [V, D]
        item_ids = jnp.asarray(self.index.ids.astype(np.int32))  # [V]
        field = int(self.field_size)
        hist_len = int(self.hist_len)
        tower_params = self.tower_params
        user_fn = self.tower_model.user_embed
        b, n, kk = key[0], int(retrieve_k), int(k)
        fit = min(int(seq_len), hist_len)

        def prog(hist_ids, hist_mask, feat_ids, feat_vals):
            users = user_fn(tower_params, hist_ids, hist_mask)   # [B, D]
            _, rows = jax.lax.top_k(users @ mat.T, n)            # [B, N]
            cands = item_ids[rows]                               # [B, N]
            ids = jnp.broadcast_to(feat_ids[:, None, :], (b, n, field))
            ids = ids.at[:, :, ITEM_SLOT].set(cands)
            vals = jnp.broadcast_to(feat_vals[:, None, :], (b, n, field))
            if hist_len:
                # static _fit_history: keep the most recent tail, zero-pad
                h_ids = jnp.zeros((b, hist_len), jnp.int32)
                h_ids = h_ids.at[:, :fit].set(hist_ids[:, seq_len - fit:])
                h_mask = jnp.zeros((b, hist_len), jnp.float32)
                h_mask = h_mask.at[:, :fit].set(
                    hist_mask[:, seq_len - fit:])
                ids = jnp.concatenate(
                    [ids, jnp.broadcast_to(h_ids[:, None, :],
                                           (b, n, hist_len))], axis=2)
                vals = jnp.concatenate(
                    [vals, jnp.broadcast_to(h_mask[:, None, :],
                                            (b, n, hist_len))], axis=2)
            probs = raw(ids.reshape(b * n, -1).astype(jnp.int32),
                        vals.reshape(b * n, -1).astype(jnp.float32))
            if isinstance(probs, dict):
                raise CannotFuse(
                    "fused cascade needs a single-output ranker; "
                    "multitask artifacts use the staged path")
            probs = jnp.reshape(probs, (b, n))
            top_p, top_i = jax.lax.top_k(probs, kk)
            top_ids = jnp.take_along_axis(cands, top_i, axis=1)
            return top_ids, top_p

        fn = jax.jit(prog)
        self._fused_cache[key] = fn
        return fn


class CascadeEngine:
    """Two-stage serving over the publish/hot-swap machinery.

    ``recommend(hist_ids, hist_mask, feat_ids, feat_vals, k)``:

      1. user tower embeds the history;
      2. the candidate index retrieves ``retrieve_k`` item ids;
      3. each candidate is substituted into the request's item slot
         (field ``ITEM_SLOT``), history packed alongside, and the batch
         ranked through the inner :class:`ServingEngine` (dynamic batching
         + bucketed shapes + backpressure all apply);
      4. the top ``k`` candidates by ranker probability come back.

    An empty history is legal end-to-end: the user tower pools zeros (the
    index then returns ITS notion of head items) and the ranker's attention
    contributes exact zeros — finite probabilities, never NaN (the
    masked-softmax regression the drill pins).

    **Overload plane.** ``slo_ms``/``shed_watermark`` build an
    :class:`~deepfm_tpu.serve.admission.AdmissionController` for the inner
    ranking engine (low-value requests get a typed ``AdmissionShed``).
    ``degrade_retrieve_k`` > 0 additionally arms the graceful-degradation
    ladder: under pressure ``recommend`` first shrinks the candidate set to
    ``degrade_retrieve_k`` (rung ``reduced_retrieve``), then skips the
    ranker entirely and answers in retrieval order (rung
    ``retrieval_only`` — scores are the index's inner-product scores, NOT
    calibrated probabilities). Every rung change is a counted, span-traced
    transition; per-request degradation is counted per rung.
    """

    def __init__(self, publish_dir: str, *, retrieve_k: int = 50,
                 poll_secs: float = 2.0, max_batch: int = 256,
                 max_delay_ms: float = 5.0,
                 buckets: Optional[Sequence[int]] = None,
                 queue_rows: int = 0,
                 slo_ms: float = 0.0, shed_watermark: int = 0,
                 degrade_retrieve_k: int = 0,
                 fused: bool = False,
                 user_cache_rows: int = 0,
                 cache_rows: int = 0, cache_ttl_s: float = 0.0,
                 coalesce: bool = False,
                 watcher_kw: Optional[dict] = None,
                 engine_kw: Optional[dict] = None):
        if retrieve_k < 1:
            raise ValueError("retrieve_k must be >= 1")
        if degrade_retrieve_k < 0 or degrade_retrieve_k > retrieve_k:
            raise ValueError(
                f"degrade_retrieve_k must be in 0..retrieve_k="
                f"{retrieve_k}, got {degrade_retrieve_k}")
        if user_cache_rows < 0:
            raise ValueError(
                f"user_cache_rows must be >= 0, got {user_cache_rows}")
        self.retrieve_k = int(retrieve_k)
        self.degrade_retrieve_k = int(degrade_retrieve_k)
        resolved = tuple(buckets) if buckets is not None \
            else export_lib.serving_buckets(max_batch)
        stats = ServingStats()
        wkw = {"poll_secs": poll_secs}
        wkw.update(watcher_kw or {})  # caller overrides (tests drive polls)
        self._watcher = export_lib.LatestWatcher(
            publish_dir,
            loader=lambda path: CascadeModel(path, buckets=resolved),
            on_swap=lambda path: stats.record_swap(),
            **wkw)
        ekw = dict(engine_kw or {})
        if (slo_ms > 0 or shed_watermark > 0) \
                and "admission" not in ekw and "admission_kw" not in ekw:
            ekw["admission_kw"] = {"slo_ms": slo_ms,
                                   "shed_watermark": shed_watermark}
        # Fast-path levers forward to the inner ranking engine: the result
        # cache there caches whole ranking batches under the same
        # (version, fingerprint) law as standalone serving.
        ekw.setdefault("cache_rows", cache_rows)
        ekw.setdefault("cache_ttl_s", cache_ttl_s)
        ekw.setdefault("coalesce", coalesce)
        self._engine = ServingEngine(
            self._watcher, max_batch=max_batch, max_delay_ms=max_delay_ms,
            buckets=resolved, queue_rows=queue_rows, stats=stats, **ekw)
        # Fused device program (opt-in; falls back per-model on any
        # structural fusion failure) + the per-user tower-embedding cache.
        self.fused = bool(fused)
        self._fused_buckets = resolved
        self.fused_calls = 0
        self._user_cache = ResultCache(user_cache_rows) \
            if user_cache_rows > 0 else None
        self.user_cache_hits = 0
        self.user_cache_misses = 0
        self._fast_lock = threading.Lock()
        stats.set_policy(serve_fused_cascade=self.fused,
                         serve_cache_user_rows=int(user_cache_rows))
        self._ladder: Optional[DegradationLadder] = None
        if self.degrade_retrieve_k > 0:
            self._ladder = DegradationLadder(stats=stats)
            # Without an admission gate the ladder still needs a pressure
            # scale: the same watermark default (half the queue).
            self._degrade_watermark = (
                self._engine.admission.shed_watermark
                if self._engine.admission is not None
                else max(1, int(shed_watermark)
                         or self._engine.queue_rows // 2))

    @property
    def watcher(self) -> export_lib.LatestWatcher:
        return self._watcher

    @property
    def engine(self) -> ServingEngine:
        return self._engine

    @property
    def stats(self) -> ServingStats:
        return self._engine.stats

    def current(self) -> CascadeModel:
        model = self._watcher._fn
        if model is None:
            raise RuntimeError("no cascade artifact published yet")
        return model

    # ----------------------------------------------------- degraded modes
    @property
    def ladder(self) -> Optional[DegradationLadder]:
        return self._ladder

    def _pressure(self) -> float:
        """The ladder's drive signal: the admission controller's combined
        depth+delay pressure when one is armed, raw queue depth over the
        degrade watermark otherwise."""
        pending = self._engine.pending_rows
        adm = self._engine.admission
        if adm is not None:
            return adm.pressure(pending)
        return pending / self._degrade_watermark

    def ladder_rung(self) -> int:
        """Advance the degradation ladder against CURRENT pressure and
        return the rung (0 = full cascade). Called per recommend(); also
        callable idle (the drill uses it to observe recovery after a
        chaos window drains)."""
        if self._ladder is None:
            return 0
        return self._ladder.update(self._pressure())

    # ------------------------------------------------------------- serving
    def _user_embed(self, model: CascadeModel, hist_ids: np.ndarray,
                    hist_mask: np.ndarray) -> np.ndarray:
        """User-tower embedding with the per-user cache in front: keyed
        ``(artifact path, fingerprint(history))`` so a hot swap — a new
        path — invalidates every cached embedding for free, exactly like
        the result cache's version key. Hits return bit-identical copies
        of the tower's output; a Zipf head user pays the tower once per
        artifact version instead of once per request."""
        if self._user_cache is None:
            return model.user_embed(hist_ids, hist_mask)
        fp = request_fingerprint(hist_ids, hist_mask)
        hit = self._user_cache.get(model.path, fp)
        if hit is not None:
            with self._fast_lock:
                self.user_cache_hits += 1
            trace_lib.instant("serve.cache", event="user_hit",
                              rows=int(hist_ids.shape[0]))
            return hit
        users = model.user_embed(hist_ids, hist_mask)
        self._user_cache.put(model.path, fp, users,
                             int(hist_ids.shape[0]))
        with self._fast_lock:
            self.user_cache_misses += 1
        return users

    def retrieve(self, hist_ids: np.ndarray, hist_mask: np.ndarray,
                 k: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Retrieval stage only: (item_ids [B, k], scores [B, k])."""
        model = self.current()
        hist_ids = np.atleast_2d(np.asarray(hist_ids, np.int32))
        hist_mask = np.atleast_2d(np.asarray(hist_mask, np.float32))
        users = self._user_embed(model, hist_ids, hist_mask)
        return model.index.search(users, k or self.retrieve_k)

    def recommend(self, hist_ids: np.ndarray, hist_mask: np.ndarray,
                  feat_ids: np.ndarray, feat_vals: np.ndarray, *,
                  k: int = 10, timeout: Optional[float] = 30.0,
                  value: str = VALUE_DEFAULT
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """ONE user's end-to-end recommendation: (item_ids [k], probs [k]).

        ``hist_ids``/``hist_mask`` [L]; ``feat_ids``/``feat_vals`` [F] the
        request context (field ``ITEM_SLOT`` is overwritten per candidate).
        The SAME loaded model version serves both stages of this request
        even if a hot swap lands mid-flight. ``value`` is the admission
        value class of the inner ranking request.

        With the degradation ladder armed, an over-budget fleet answers
        degraded instead of failing: rung 1 ranks only
        ``degrade_retrieve_k`` candidates; rung 2 skips the ranker and the
        returned scores are RETRIEVAL scores (inner products), not
        probabilities — callers can tell from the counted, traced rung.
        """
        model = self.current()
        hist_ids = np.asarray(hist_ids, np.int32).reshape(1, -1)
        hist_mask = np.asarray(hist_mask, np.float32).reshape(1, -1)
        feat_ids = np.asarray(feat_ids, np.int32).reshape(-1)
        feat_vals = np.asarray(feat_vals, np.float32).reshape(-1)
        if feat_ids.shape[0] != model.field_size:
            raise ValueError(
                f"expected {model.field_size} context fields, "
                f"got {feat_ids.shape[0]}")
        rung = self.ladder_rung()
        if rung == 0 and self.fused and model.supports_fused:
            try:
                ids_k, probs_k = self._recommend_fused(
                    model, hist_ids, hist_mask, feat_ids[None],
                    feat_vals[None], k)
                return ids_k[0], probs_k[0]
            except CannotFuse:
                model.fused_failed = True
                trace_lib.instant("serve.cascade_fused", event="fallback")
        retrieve_k = self.retrieve_k if rung == 0 \
            else self.degrade_retrieve_k
        users = self._user_embed(model, hist_ids, hist_mask)
        cand_ids, cand_scores = model.index.search(users, retrieve_k)
        cand_ids = cand_ids[0]                              # [N]
        n = cand_ids.shape[0]
        if rung > 0:
            self.stats.record_degraded(DEGRADE_RUNGS[rung])
        if rung >= 2:
            # retrieval_only: serve the index's order — the request costs
            # one tower embed + one ANN search, no ranking flush at all.
            k = min(int(k), n)
            return cand_ids[:k], cand_scores[0][:k]
        ids = np.tile(feat_ids, (n, 1)).astype(np.int32)    # [N, F]
        vals = np.tile(feat_vals, (n, 1)).astype(np.float32)
        ids[:, ITEM_SLOT] = cand_ids
        if model.hist_len:
            h_ids, h_mask = _fit_history(hist_ids[0], hist_mask[0],
                                         model.hist_len)
            ids = np.concatenate(
                [ids, np.tile(h_ids, (n, 1))], axis=1)
            vals = np.concatenate(
                [vals, np.tile(h_mask, (n, 1))], axis=1)
        probs = np.asarray(
            self._engine.predict(ids, vals, timeout=timeout,
                                 value=value)).reshape(-1)
        k = min(int(k), n)
        top = np.argsort(-probs, kind="stable")[:k]
        return cand_ids[top], probs[top]

    # ----------------------------------------------------- fused fast path
    def _recommend_fused(self, model: CascadeModel, hist_ids: np.ndarray,
                         hist_mask: np.ndarray, feat_ids: np.ndarray,
                         feat_vals: np.ndarray, k: int
                         ) -> Tuple[np.ndarray, np.ndarray]:
        """Run [B] users through the single fused device program. The
        batch pads up to the engine's pow2 bucket ladder (pad users are
        all-zeros and sliced away), so at most ``len(buckets)`` programs
        compile per (seq_len, retrieve_k, k) — the same bounded-compile
        discipline as the staged ranker. Completions are recorded into
        the SAME stats reservoirs as staged requests."""
        t0 = time.monotonic()
        b = int(hist_ids.shape[0])
        bucket = export_lib.next_bucket(b, self._fused_buckets)
        if bucket != b:
            hist_ids = np.concatenate(
                [hist_ids, np.zeros((bucket - b,) + hist_ids.shape[1:],
                                    np.int32)])
            hist_mask = np.concatenate(
                [hist_mask, np.zeros((bucket - b,) + hist_mask.shape[1:],
                                     np.float32)])
            feat_ids = np.concatenate(
                [feat_ids, np.zeros((bucket - b,) + feat_ids.shape[1:],
                                    np.int32)])
            feat_vals = np.concatenate(
                [feat_vals, np.zeros((bucket - b,) + feat_vals.shape[1:],
                                     np.float32)])
        n = min(self.retrieve_k, model.index.num_items)
        kk = min(int(k), n)
        fn = model.fused_program(bucket, int(hist_ids.shape[1]), n, kk)
        top_ids, top_p = fn(hist_ids.astype(np.int32),
                            hist_mask.astype(np.float32),
                            feat_ids.astype(np.int32),
                            feat_vals.astype(np.float32))
        top_ids = np.asarray(top_ids)[:b]
        top_p = np.asarray(top_p)[:b]
        lat_ms = 1000.0 * (time.monotonic() - t0)
        with self._fast_lock:
            self.fused_calls += 1
        for _ in range(b):
            self.stats.record_request_done(lat_ms)
        # int64 ids on the way out, matching the staged index.search dtype
        return top_ids.astype(np.int64), top_p

    def recommend_batch(self, hist_ids: np.ndarray, hist_mask: np.ndarray,
                        feat_ids: np.ndarray, feat_vals: np.ndarray, *,
                        k: int = 10, timeout: Optional[float] = 30.0,
                        value: str = VALUE_DEFAULT
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """[B] users end-to-end at once: (item_ids [B, k], probs [B, k]).

        With the fused program armed (``fused=True`` and a fusable
        artifact, full-cascade rung) the whole batch is ONE device
        dispatch; otherwise each row runs the staged path. Output is
        row-for-row what per-row :meth:`recommend` returns — same items,
        probabilities to float ULP (batching changes XLA's row
        vectorization; the B=1 fused path is bit-equal to staged,
        pinned in ``tests/test_cascade.py``)."""
        hist_ids = np.atleast_2d(np.asarray(hist_ids, np.int32))
        hist_mask = np.atleast_2d(np.asarray(hist_mask, np.float32))
        feat_ids = np.atleast_2d(np.asarray(feat_ids, np.int32))
        feat_vals = np.atleast_2d(np.asarray(feat_vals, np.float32))
        model = self.current()
        if self.fused and model.supports_fused and self.ladder_rung() == 0:
            try:
                return self._recommend_fused(model, hist_ids, hist_mask,
                                             feat_ids, feat_vals, k)
            except CannotFuse:
                model.fused_failed = True
                trace_lib.instant("serve.cascade_fused", event="fallback")
        out_ids, out_ps = [], []
        for i in range(hist_ids.shape[0]):
            ids_i, p_i = self.recommend(
                hist_ids[i], hist_mask[i], feat_ids[i], feat_vals[i],
                k=k, timeout=timeout, value=value)
            out_ids.append(ids_i)
            out_ps.append(p_i)
        return np.stack(out_ids), np.stack(out_ps)

    # ----------------------------------------------------------- lifecycle
    def close(self, timeout: Optional[float] = None) -> None:
        self._engine.close(timeout=timeout)
        self._watcher.close()

    def __enter__(self) -> "CascadeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _fit_history(hist_ids: np.ndarray, hist_mask: np.ndarray,
                 hist_len: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad/truncate a request's history to the artifact's trained length
    (keep the most recent tail on truncation).

    Short-circuits: a history-free artifact (``hist_len`` 0 — previously
    this built and sliced zero-length scratch arrays per candidate batch)
    returns empty arrays immediately, and an already-fitting history is
    passed through without a re-fit copy."""
    hist_len = int(hist_len)
    if hist_len <= 0:
        return np.zeros((0,), np.int32), np.zeros((0,), np.float32)
    ln = hist_ids.shape[0]
    if ln == hist_len:
        return (np.asarray(hist_ids, np.int32),
                np.asarray(hist_mask, np.float32))
    out_ids = np.zeros((hist_len,), np.int32)
    out_mask = np.zeros((hist_len,), np.float32)
    n = min(ln, hist_len)
    out_ids[:n] = hist_ids[ln - n:]
    out_mask[:n] = hist_mask[ln - n:]
    return out_ids, out_mask
