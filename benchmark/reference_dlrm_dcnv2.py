"""The plain reference: MLPerf DLRM-DCNv2 in float32 ``jax.numpy``, from its
equations.

Source: github.com/mlcommons/training, ``recommendation_v2/torchrec_dlrm``
(MLPerf Training v3.0 onward): torchrec's ``DLRM_DCN`` — ``DenseArch``,
``SparseArch``, ``InteractionDCNArch`` over a ``LowRankCrossNet``, and
``OverArch``. The cross layer is Wang et al., "DCN V2" (arXiv:2008.13535),
eq. 2 in its low-rank form (eq. 3 without experts). Each line of ``logits``
names the piece of the source it repeats:

    d      = x_num[B, 13]                                (already transformed)
    b      = relu(relu(relu(d A1 + a1) A2 + a2) A3 + a3)   DenseArch: every
                                                         layer ReLU
    e_f    = E[id_f]                                     SparseArch: a bag's
                                                         sum; one id here
    x0     = concat(b, e_1, ..., e_C)                    InteractionDCNArch:
                                                         dense first
    x_l+1  = x0 * ((x_l V_l) U_l + c_l) + x_l            LowRankCrossNet
    h      = relu MLP over x_L, then one linear -> logit OverArch: the last
                                                         layer has no ReLU
    loss   = mean log-loss(logit, label)                 BCEWithLogitsLoss
    Adagrad on every parameter, the tables included; no L2, no dropout

Departures from the source, all of them the configuration's (its file lists
them under ``reduced`` and ``assumed``): one id per field where MLPerf v3.0
feeds a bag of ids (the bag's sum has one term); the numeric values arrive
already transformed; Adagrad is the program's ``optax.adagrad``: one
accumulator per element, started at ``init``, with ``eps`` inside the root,

    s <- s + g*g;   p <- p - lr * g / sqrt(s + eps)

(the source's dense Adagrad is torch's: accumulator 0, ``eps`` outside the
root; its tables take a row-wise Adagrad).

It imports nothing of ``deepfm_tpu`` and is handed nothing the program made.
It holds only the table rows it is given (``rows``): Adagrad treats each
element by itself and there is no L2 term, so a row the batch does not touch
has a zero gradient and does not move at all — following a subset of rows is
exact, and an untouched row's change is exactly 0.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import log_loss, split_tables, worst_leaf_gap

Params = Dict[str, jax.Array]

TABLE_LEAVES = ("fm_v",)


def logits(params: Params, ids: jax.Array, dense: jax.Array, *,
           n_bottom: int, n_cross: int, n_top: int) -> jax.Array:
    """[B] logits. ``ids`` [B, C] index the rows held in ``params["fm_v"]``;
    ``dense`` [B, N] are the numeric values."""
    h = dense.astype(jnp.float32)
    for i in range(n_bottom):                                   # DenseArch
        h = jax.nn.relu(h @ params[f"bottom.layers.{i}.w"]
                        + params[f"bottom.layers.{i}.b"])
    e = params["fm_v"][ids]                                     # SparseArch
    x0 = jnp.concatenate([h, e.reshape(e.shape[0], -1)], axis=1)
    x = x0
    for l in range(n_cross):                                    # LowRankCrossNet
        x = x0 * ((x @ params[f"cross.{l}.v"]) @ params[f"cross.{l}.u"]
                  + params[f"cross.{l}.b"]) + x
    h = x
    for i in range(n_top):                                      # OverArch
        h = jax.nn.relu(h @ params[f"tower.layers.{i}.w"]
                        + params[f"tower.layers.{i}.b"])
    return (h @ params["tower.out.w"])[:, 0] + params["tower.out.b"][0]


class Follower:
    """Follows training steps on the table rows ``rows`` (sorted, unique)
    and on every dense parameter, from the parameters ``params0``."""

    def __init__(self, params0: Dict[str, np.ndarray], rows: np.ndarray, *,
                 n_bottom: int, n_cross: int, n_top: int,
                 learning_rate: float, adagrad_init: float,
                 adagrad_eps: float):
        self.rows = np.asarray(rows)
        self.shape = dict(n_bottom=int(n_bottom), n_cross=int(n_cross),
                          n_top=int(n_top))
        self.lr = float(learning_rate)
        # The accumulator is float32: its start is that type's nearest value.
        self.init = float(np.float32(adagrad_init))
        self.eps = float(adagrad_eps)
        self.params: Params = {k: jnp.asarray(v, jnp.float32)
                               for k, v in params0.items()}
        self.s = jax.tree.map(lambda p: jnp.full_like(p, self.init),
                              self.params)
        self.count = 0
        with jax.default_matmul_precision("highest"):
            self._step = jax.jit(self._step_impl)

    def _loss(self, params, ids, dense, label):
        return log_loss(logits(params, ids, dense, **self.shape), label)

    def _step_impl(self, params, s, ids, dense, label):
        xent, g = jax.value_and_grad(self._loss)(params, ids, dense, label)
        s = jax.tree.map(lambda a, b: a + b * b, s, g)
        params = jax.tree.map(
            lambda p, a, b: p - self.lr * b / jnp.sqrt(a + self.eps),
            params, s, g)
        return params, s, xent

    #: ids -> positions in ``rows``; raises on a row the reference lacks.
    local_ids = reference.Follower.local_ids

    def step(self, cat_ids: np.ndarray, dense: np.ndarray,
             label: np.ndarray) -> float:
        """One optimizer step; returns the step's log-loss (before it)."""
        self.count += 1
        with jax.default_matmul_precision("highest"):
            self.params, self.s, xent = self._step(
                self.params, self.s, jnp.asarray(self.local_ids(cat_ids)),
                jnp.asarray(dense, jnp.float32),
                jnp.asarray(label, jnp.float32).reshape(-1))
        return float(xent)

    def sum_of_squares(self) -> Dict[str, np.ndarray]:
        """What Adagrad added to its accumulator: the squared gradients of
        the steps so far, summed."""
        return {k: np.asarray(v, np.float64) - self.init
                for k, v in self.s.items()}


def dispatch_gaps(got_params: dict, got_s: dict, got_xent: float,
                  want: Follower, want_xent: float, params0: dict,
                  tables, n_real: int, touched: np.ndarray) -> dict:
    """The numbers the cell is judged by, for one dispatch of steps: the gap
    in the last step's log-loss; by the worst leaf the gaps in Adagrad's sum
    of squared gradients (the gradients as the optimizer got them) and in
    the parameters' change; and how many elements of the untouched rows the
    program moved or gave a gradient (the mathematics says none)."""
    def cut(tree, minus=None):
        return split_tables({k: np.asarray(v) for k, v in tree.items()},
                            tables, n_real, touched, minus)

    init = {k: np.float64(want.init) for k in got_s}
    s_got = cut(got_s, init)
    d_got = cut(got_params, params0)
    s_gap, s_leaf = worst_leaf_gap(s_got, cut(want.sum_of_squares()))
    d_gap, d_leaf = worst_leaf_gap(d_got, cut(want.params, params0))
    moved = sum(int(np.count_nonzero(tree[name + "[untouched]"]))
                for tree in (s_got, d_got) for name in tables)
    return {"xent_gap": abs(float(got_xent) - float(want_xent)),
            "accumulator_gap": s_gap, "accumulator_leaf": s_leaf,
            "param_change_gap": d_gap, "param_change_leaf": d_leaf,
            "untouched_rows_moved": moved}
