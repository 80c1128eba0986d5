"""The plain reference: DeepFM in float32 ``jax.numpy``, from its equations.

Guo et al., "DeepFM: A Factorization-Machine based Neural Network for CTR
Prediction" (IJCAI 2017), with the reference recipe's loss and optimizer:

    e_f     = V[id_f] * val_f                                  (F vectors of K)
    y_first = sum_f W[id_f] * val_f
    y_fm    = 1/2 sum_k ((sum_f e_fk)^2 - sum_f e_fk^2)
    h_0     = concat_f e_f;  h_{l+1} = dropout(relu(h_l A_l + b_l))
    logit   = b + y_first + y_fm + h_L a_out + b_out
    loss    = mean log-loss(logit, label) + l2/2 * sum(W^2 + V^2)
    Adam(lr, 0.9, 0.999, 1e-8) on every parameter, the tables included

It imports nothing of ``deepfm_tpu`` and is handed nothing the program made.
It holds only the table rows it is given (``rows``): dense Adam treats each
row by itself, so following a subset of rows is exact for those rows — the
rows a batch touches get the data gradient plus the L2 pull, every other row
the L2 pull alone. Dropout masks are an input, because a mask is part of the
step's data, not of its arithmetic.

"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jax.Array]

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


TABLE_LEAVES = ("fm_w", "fm_v")


def logits(params: Params, ids: jax.Array, vals: jax.Array, *,
           n_layers: int, keep: Sequence[float] = (),
           masks: Optional[Sequence[jax.Array]] = None) -> jax.Array:
    """[B] logits. ``ids`` index the rows held in ``params``' tables;
    ``masks`` (one bool [B, width] per hidden layer) switch dropout on."""
    vals = vals.astype(jnp.float32)
    w = params["fm_w"][ids]                            # [B, F]
    e = params["fm_v"][ids] * vals[..., None]          # [B, F, K]
    y_first = jnp.sum(w * vals, axis=1)
    s = jnp.sum(e, axis=1)
    y_fm = 0.5 * jnp.sum(s * s - jnp.sum(e * e, axis=1), axis=1)
    h = e.reshape(e.shape[0], -1)
    for i in range(n_layers):
        h = jax.nn.relu(h @ params[f"tower.layers.{i}.w"]
                        + params[f"tower.layers.{i}.b"])
        if masks is not None and keep[i] < 1.0:
            h = jnp.where(masks[i], h / keep[i], 0.0)
    y_deep = (h @ params["tower.out.w"])[:, 0] + params["tower.out.b"][0]
    return params["fm_b"][0] + y_first + y_fm + y_deep


def log_loss(z: jax.Array, label: jax.Array) -> jax.Array:
    """Mean of -[y log s(z) + (1-y) log(1-s(z))], in its stable form."""
    return jnp.mean(jnp.maximum(z, 0.0) - z * label
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))


class Follower:
    """Follows training steps on the table rows ``rows`` (sorted, unique)
    and on every dense parameter, from the parameters ``params0``."""

    def __init__(self, params0: Dict[str, np.ndarray], rows: np.ndarray, *,
                 n_layers: int, keep: Sequence[float], l2_reg: float,
                 learning_rate: float):
        self.rows = np.asarray(rows)
        self.n_layers = int(n_layers)
        self.keep = tuple(float(k) for k in keep)
        self.lr = float(learning_rate)
        self.l2 = float(l2_reg)
        self.params: Params = {k: jnp.asarray(v, jnp.float32)
                               for k, v in params0.items()}
        self.m = jax.tree.map(jnp.zeros_like, self.params)
        self.v = jax.tree.map(jnp.zeros_like, self.params)
        self.count = 0
        with jax.default_matmul_precision("highest"):
            self._step = jax.jit(self._step_impl)

    def _loss(self, params, ids, vals, label, masks):
        z = logits(params, ids, vals, n_layers=self.n_layers, keep=self.keep,
                   masks=masks)
        xent = log_loss(z, label)
        reg = 0.5 * sum(jnp.sum(jnp.square(params[k])) for k in TABLE_LEAVES)
        return xent + self.l2 * reg, xent

    def _step_impl(self, params, m, v, t, ids, vals, label, masks):
        (_, xent), g = jax.value_and_grad(self._loss, has_aux=True)(
            params, ids, vals, label, masks)
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b,
                         v, g)
        c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
        params = jax.tree.map(
            lambda p, a, b: p - self.lr * (a / c1) / (jnp.sqrt(b / c2)
                                                     + ADAM_EPS),
            params, m, v)
        return params, m, v, xent

    def local_ids(self, feat_ids: np.ndarray) -> np.ndarray:
        idx = np.searchsorted(self.rows, feat_ids)
        if not np.array_equal(self.rows[np.minimum(idx, len(self.rows) - 1)],
                              feat_ids):
            raise ValueError("a batch touches a row the reference lacks")
        return idx.astype(np.int32)

    def step(self, feat_ids: np.ndarray, feat_vals: np.ndarray,
             label: np.ndarray, masks: Optional[List[np.ndarray]]) -> float:
        """One optimizer step; returns the step's log-loss (before it)."""
        self.count += 1
        with jax.default_matmul_precision("highest"):
            self.params, self.m, self.v, xent = self._step(
                self.params, self.m, self.v, jnp.float32(self.count),
                jnp.asarray(self.local_ids(feat_ids)),
                jnp.asarray(feat_vals, jnp.float32),
                jnp.asarray(label, jnp.float32).reshape(-1),
                None if masks is None else [jnp.asarray(x) for x in masks])
        return float(xent)


def predict_logits(params0: Dict[str, np.ndarray], rows: np.ndarray,
                   feat_ids: np.ndarray, feat_vals: np.ndarray, *,
                   n_layers: int) -> np.ndarray:
    """Inference logits for ``feat_ids`` given the table rows ``rows``."""
    idx = np.searchsorted(rows, feat_ids).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        z = jax.jit(lambda p, i, x: logits(p, i, x, n_layers=n_layers))(
                {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()},
                jnp.asarray(idx), jnp.asarray(feat_vals, jnp.float32))
    return np.asarray(z, np.float64)


def worst_leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                   ) -> Tuple[float, str]:
    """The largest, over leaves, of | ||got|| - ||want|| | measured against
    ||want|| of that leaf or of the median leaf, whichever is larger (some
    leaves are all but zero). Returns (gap, name of the worst leaf)."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in want.items()}
    floor = float(np.median(list(norms.values())))
    worst, name = 0.0, ""
    for k, n_want in norms.items():
        n_got = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        gap = abs(n_got - n_want) / max(n_want, floor, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, name = gap, k
    return worst, name


def split_tables(tree: Dict[str, np.ndarray], tables, n_real: int,
                 touched: np.ndarray, minus: Optional[dict] = None
                 ) -> Dict[str, np.ndarray]:
    """Leaves as they are compared: each table cut to the ``n_real`` rows
    really held and split into the rows the batches touched and the others
    (which only the L2 term moves); ``minus`` is subtracted first."""
    out = {}
    for name, x in tree.items():
        x = np.asarray(x, np.float64)
        if minus is not None:
            x = x - np.asarray(minus[name], np.float64)
        if name in tables:
            out[name + "[touched]"] = x[:n_real][touched]
            out[name + "[untouched]"] = x[:n_real][~touched]
        else:
            out[name] = x
    return out


def dispatch_gaps(got_params: dict, got_mu: dict, got_xent: float,
                  want: "Follower", want_xent: float, params0: dict,
                  tables, n_real: int, touched: np.ndarray) -> dict:
    """The three numbers a training cell is judged by, for one dispatch of
    steps: the gap in the last step's log-loss, and by the worst leaf the
    gaps in Adam's first moment (the gradients as the optimizer got them)
    and in the parameters' change."""
    def cut(tree, minus=None):
        return split_tables({k: np.asarray(v) for k, v in tree.items()},
                            tables, n_real, touched, minus)

    mu_gap, mu_leaf = worst_leaf_gap(cut(got_mu), cut(want.m))
    delta_gap, delta_leaf = worst_leaf_gap(cut(got_params, params0),
                                           cut(want.params, params0))
    return {"xent_gap": abs(float(got_xent) - float(want_xent)),
            "first_moment_gap": mu_gap, "first_moment_leaf": mu_leaf,
            "param_change_gap": delta_gap, "param_change_leaf": delta_leaf}
