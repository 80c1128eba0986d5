"""Latent-attention mixture-of-experts decoder with a multi-token-prediction
module (``--model glm4_moe_lite``).

``models.kimi_linear``'s stack (pre-norm residual blocks of RMSNorm, a mixer
and a feed-forward, every block recomputed in the backward pass; next-token
cross-entropy the model owns; ``hist_ids`` [B, L] the tokens, ``tok_emb`` the
table, an untied ``head``) with what GLM-4.7-Flash (``model_type:
glm4_moe_lite``) changes, and nothing written a second time: the layers'
loop, the dense SwiGLU, the expert layer, the router, the shared expert, the
masked scores, rotary, the head's chunked loss and the counts are
``kimi_linear``'s and ``sdar_moe``'s, the selection bias in the model state
``lfm2_moe.SelectionBias``'s, by inheritance and import.
``benchmark/reference_glm4_moe_lite.py`` holds the equations.

* **Every layer mixes by latent attention as the DeepSeek-V2/V3 family runs
  it** (``mla_mixer``, scopes ``attn`` / ``attn_scores``): queries through a
  normed bottleneck of ``--mla_q_rank`` (``c_q = RMSNorm(xn W_qa)``, ``q =
  c_q W_qb``); a head's query and key are ``--mla_nope_dim`` columns of its
  own, not rotated, beside ``--mla_rope_dim`` rotated ones (rotate-half,
  ``--rope_theta``), the key's rotated part one vector every head shares,
  taken from ``xn W_kva`` beside the ``--mla_latent_dim`` latent; keys' own
  columns and values (``--mla_value_dim`` wide) are expanded from the normed
  latent (the training form: no absorbed form, no latent cache). The scores
  are ``sdar_moe.masked_scores`` under ``kimi_linear.causal`` at
  ``1 / sqrt(nope + rope)``: on a TPU, at keys and values of whole or half
  lane lines, the block kernel, one key/value head a query head; elsewhere
  XLA's chunked path (``attn_scores_by``, said in ``step_notes``).
  ``--attn_head_dim`` is not read: the three widths are their own flags.
* **Dense, then sparse.** The first ``--dense_layers`` layers feed forward
  through a dense SwiGLU, the others through the expert layer beside a
  shared expert: sigmoid scores, the ``--moe_top_k`` largest of score +
  bias, weights the chosen scores over their sum (+ ``RENORM_EPS``) times
  ``--moe_route_scale``. The bias is ``lfm2_moe.SELECT_BIAS`` in the model
  state, constant (ROADMAP B7(d)); ``moe_bias_moved_picks`` counts the
  selections it changed.
* **The multi-token-prediction module** (``--mtp_depth`` 1, scope ``mtp``;
  the DeepSeek-V3 module): ``m_i = [RMSNorm(Emb(t_{i+1}); enorm) ;
  RMSNorm(h_i; hnorm)] W_eh`` with h the last layer's output ahead of the
  final norm, one more whole block (latent attention at positions i + 1,
  an expert layer with a router, experts and a bias row of its own:
  ``params['mtp']['block']``), then **the main model's** head under the
  module's own norm: the cross-entropy of ``t_{i+2}``, mean over i = 0 ..
  L-3 (scope ``mtp_head``). ``Emb(t_{i+1})`` is the looked-up rows of the
  step rolled by one position, not a second lookup; the module runs over
  all L positions (whole chunks and kernel blocks), the last two weighing
  nothing, and is causal, so what the roll wraps around reaches no loss.
  ``tok_emb`` and ``head`` are each used twice; their cotangents sum by AD.
* **The loss** a sequence is ``L1 + mtp_loss_weight * L2``: what
  ``per_example_loss`` returns and the trainer differentiates (and what an
  evaluation reports). The two parts ride the model state and the step's
  metrics (``loss_parts``): ``xent`` the main model's ``L1``, which takes
  the place of the trainer's own mean in the metrics, and ``mtp_xent``.

A share of a layer is told as in ``kimi_linear``: ``--attn_q_heads`` heads
(``W_qb``'s, ``W_kvb``'s and ``W_o``'s are here), ``--moe_experts_held``
experts from ``--moe_first_expert`` on; ``W_qa``, ``W_kva``, their norms, the
router, the shared expert, the dense MLP and ``W_eh`` whole; ``W_o``'s and
the experts' partial sums unreduced.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common
from .kimi_linear import KimiLinear, causal
from .lfm2_moe import SelectionBias
from .sdar_moe import (_dot, _operand, attn_notes, attn_scores_by,
                       masked_scores, rms_norm, rotary, route, weighted_nll)

#: epsilon beside the chosen scores' sum where they are renormalised
RENORM_EPS = 1e-20
#: The loss's parts in the model state and the step's metrics.
XENT, MTP_XENT = "xent", "mtp_xent"


def layer_kinds(cfg: Any) -> Tuple[Tuple[str, str], ...]:
    """((mixer, feed-forward) of each layer): every layer mixes by latent
    attention; the first ``dense_layers`` feed forward through a dense MLP,
    the rest through the expert layer."""
    return tuple(("mla", "mlp" if i < cfg.dense_layers else "moe")
                 for i in range(cfg.decoder_layers))


@jax.named_scope("attn")
def mla_mixer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *, nope_dim: int,
              rope_dim: int, theta: float, eps: float, cdt: jnp.dtype,
              scores_by: str = "xla", position0: int = 0) -> jnp.ndarray:
    """The held heads' part of ``MLA(RMSNorm(x))`` (the module's
    docstring): x [B, S, d] at positions ``position0 ..`` -> [B, S, d]
    (``mla_wo``'s sum over the held heads, unreduced). ``lp['mla_w_qb']``
    says how many heads are held, ``lp['mla_w_kvb']`` how wide a value is.
    ``scores_by`` is ``sdar_moe.attn_scores_by``'s word."""
    b, s, _ = x.shape
    xn = rms_norm(x, lp["norm1"], eps)
    positions = position0 + jnp.arange(s)
    c_q = rms_norm(_dot(xn, lp["mla_w_qa"], cdt), lp["mla_q_norm"], eps)
    q = _dot(c_q, lp["mla_w_qb"], cdt).reshape(b, s, -1, nope_dim + rope_dim)
    h = q.shape[2]
    q = jnp.concatenate([q[..., :nope_dim],
                         rotary(q[..., nope_dim:], positions, theta)], -1)
    kva = _dot(xn, lp["mla_w_kva"], cdt)
    latent = rms_norm(kva[..., :-rope_dim], lp["mla_kv_norm"], eps)
    kv = _dot(latent, lp["mla_w_kvb"], cdt).reshape(b, s, h, -1)
    shared = rotary(kva[..., None, -rope_dim:], positions, theta)
    k = jnp.concatenate([kv[..., :nope_dim], jnp.broadcast_to(
        shared, (b, s, h, rope_dim))], -1)
    with jax.named_scope("attn_scores"):
        out = masked_scores(q, _operand(k, cdt),
                            _operand(kv[..., nope_dim:], cdt), mask=causal,
                            cdt=cdt, scores_by=scores_by)
    return _dot(out, lp["mla_wo"], cdt)


@jax.named_scope("mtp")
def mtp_input(mp: Dict[str, jnp.ndarray], emb: jnp.ndarray, h: jnp.ndarray,
              *, eps: float, cdt: jnp.dtype) -> jnp.ndarray:
    """``[RMSNorm(Emb(t_{i+1}); enorm) ; RMSNorm(h_i; hnorm)] W_eh``: emb
    [B, L, d] the looked-up rows of the tokens (rolled here by one position:
    the last position's is the first's and reaches no loss), h [B, L, d] the
    last layer's output -> [B, L, d]."""
    return _dot(jnp.concatenate(
        [rms_norm(jnp.roll(emb, -1, axis=1), mp["enorm"], eps),
         rms_norm(h, mp["hnorm"], eps)], axis=-1), mp["w_eh"], cdt)


class Glm4MoeLite(SelectionBias, KimiLinear):
    """Latent-attention MoE decoder with a multi-token-prediction module
    over ``hist_ids``; see the module's docstring."""

    name = "glm4_moe_lite"
    _kinds = staticmethod(layer_kinds)
    #: The parts of the loss the model state and the step's metrics carry
    #: (floats; the trainer says each on ``train.log_sync``).
    loss_parts = (XENT, MTP_XENT)

    def __init__(self, cfg: Any):
        super().__init__(cfg)
        self.block_kinds = self.kinds + (("mla", "moe"),) * cfg.mtp_depth
        #: lambda: what the module's loss weighs in the step's
        self.mtp_weight = float(cfg.mtp_loss_weight)
        self.route_by = functools.partial(
            route, score=jax.nn.sigmoid, scale=cfg.moe_route_scale,
            renorm_eps=RENORM_EPS)

    def init_counts(self) -> common.State:
        return {**super().init_counts(),
                **{n: jnp.zeros((), jnp.float32) for n in self.loss_parts}}

    def _score_heads(self) -> Tuple[int, int]:
        return self.cfg.attn_q_heads, self.cfg.mla_value_dim

    def _init_mixer(self, mixer: str, glorot, keys) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        d, h, rank, latent = (cfg.embedding_size, cfg.attn_q_heads,
                              cfg.mla_q_rank, cfg.mla_latent_dim)
        nope, rope, value = (cfg.mla_nope_dim, cfg.mla_rope_dim,
                             cfg.mla_value_dim)
        return {
            "mla_w_qa": glorot(d, rank),
            "mla_q_norm": jnp.ones((rank,), jnp.float32),
            "mla_w_qb": glorot(rank, h * (nope + rope)),
            "mla_w_kva": glorot(d, latent + rope),
            "mla_kv_norm": jnp.ones((latent,), jnp.float32),
            "mla_w_kvb": glorot(latent, h * (nope + value)),
            "mla_wo": glorot(h * value, d)}

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        params, state = super().init(rng)
        if self.cfg.mtp_depth:
            d = self.cfg.embedding_size
            k_eh, k_block = jax.random.split(jax.random.fold_in(rng, 1))
            params["mtp"] = {
                "enorm": jnp.ones((d,), jnp.float32),
                "hnorm": jnp.ones((d,), jnp.float32),
                "w_eh": common.glorot_uniform(k_eh, (2 * d, d)),
                "block": self._init_layer(k_block, "mla", "moe"),
                "final_norm": jnp.ones((d,), jnp.float32)}
        return params, state

    def _with_bias(self, params: common.Params, bias: jnp.ndarray
                   ) -> common.Params:
        """... and the module's block its own, the last row."""
        params = super()._with_bias(params, bias)
        if "mtp" not in params:
            return params
        mtp = params["mtp"]
        return {**params, "mtp": {**mtp, "block": {
            **mtp["block"], "select_bias": bias[-1]}}}

    def _paths(self, ids: jnp.ndarray, one_device: bool) -> Dict[str, str]:
        cfg = self.cfg
        seq = ids.shape[1]
        # the kernel where keys and values are both of widths it takes
        widths = {cfg.mla_nope_dim + cfg.mla_rope_dim, cfg.mla_value_dim}
        scores_by = "kernel" if all(attn_scores_by(
            seq, w, one_device=one_device) == "kernel" for w in widths) \
            else "xla"
        self.step_notes.update(attn_notes(scores_by, causal, seq, 1))
        return {**self._moe_paths(ids, one_device),
                "scores_by": scores_by}

    def _mixer(self, mixer: str, lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
               scores_by: str = "xla", position0: int = 0
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        return mla_mixer(lp, x, nope_dim=cfg.mla_nope_dim,
                         rope_dim=cfg.mla_rope_dim, theta=cfg.rope_theta,
                         eps=cfg.rms_norm_eps, cdt=self.cdt,
                         scores_by=scores_by, position0=position0), {}

    def _run_layers(self, params, x, paths, keeps):
        """-> ((the last layer's output, the module's block's output or None
        without a module): the two streams the head reads, the blocks'
        counts)."""
        h, seen = super()._run_layers(params, x, paths, keeps)
        if "mtp" not in params:
            return (h, None), seen
        cfg = self.cfg
        mp = params["mtp"]
        # (made again in the backward pass, as a block is: the rolled rows
        # and the concatenation are not kept)
        m = jax.checkpoint(functools.partial(
            mtp_input, eps=cfg.rms_norm_eps, cdt=self.cdt))(
                {k: mp[k] for k in ("enorm", "hnorm", "w_eh")}, x, h)
        m, counts, _ = self._run_layer(
            len(self.kinds), self.block_kinds[-1], m, mp["block"], {},
            {**paths, "position0": 1}, keeps[-1])
        for name, value in counts.items():
            seen.setdefault(name, []).append(value)
        return (h, m), seen

    def _head_params(self, params: common.Params) -> List[Any]:
        """... and the module's pass: its own norm, the same matrix."""
        reads = super()._head_params(params)
        if "mtp" not in params:
            return reads
        return reads + [(params["mtp"]["final_norm"], params["head"])]

    @jax.named_scope("mtp_head")
    def mtp_logits(self, params: common.Params, m: jnp.ndarray
                   ) -> jnp.ndarray:
        """[..., d] of the module's block's output -> [..., V]: the
        module's own norm and the main model's head."""
        mn = rms_norm(m, params["mtp"]["final_norm"], self.cfg.rms_norm_eps)
        return _dot(mn, params["head"], self.cdt)

    def apply(self, params: common.Params, state: common.State,
              feat_ids: jnp.ndarray, feat_vals: jnp.ndarray, *,
              train: bool, rng: Optional[jax.Array] = None,
              shard_axis: Optional[str] = None,
              data_axis: Optional[str] = None,
              emb_rows: Optional[Dict[str, Any]] = None,
              emb_plan: Optional[Dict[str, Any]] = None,
              hist_ids: Optional[jnp.ndarray] = None,
              hist_mask: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, common.State]:
        """The main model's logits [B, L, V]: position i's are of token
        i + 1."""
        (h, _), _, counts = self._run(
            params, state, hist_ids, shard_axis, data_axis,
            {"emb_rows": emb_rows, "emb_plan": emb_plan})
        return self.logits(params, h), counts

    def per_example_loss(self, params: common.Params, state: common.State,
                         batch: Dict[str, jnp.ndarray], *, train: bool,
                         rng: Optional[jax.Array],
                         shard_axis: Optional[str] = None,
                         data_axis: Optional[str] = None, **emb
                         ) -> Tuple[jnp.ndarray, common.State]:
        """(``L1 + mtp_loss_weight * L2`` a sequence [B], new state): ``L1``
        the mean over positions 0 .. L-2 of the next token's cross-entropy,
        ``L2`` the mean over 0 .. L-3 of the module's, of the token after
        (the module's docstring). Both head passes run over all L positions
        in whole chunks (``weighted_nll``); the last one's, or two's,
        weights are zero. The state's ``xent`` and ``mtp_xent`` are the
        batch's means of the two."""
        (h, m), tokens, counts = self._run(
            params, state, batch["hist_ids"], shard_axis, data_axis, emb)
        length = tokens.shape[1]
        at = jnp.arange(length)

        def mean_nll(nll, logits_of, stream, ahead):
            weight = jnp.broadcast_to(
                (at < length - ahead).astype(jnp.float32), tokens.shape)
            return nll(logits_of, stream, jnp.roll(tokens, -ahead, axis=1),
                       weight) / (length - ahead)

        self.step_notes.update(self._head_notes(params, tokens))
        xent = mean_nll(weighted_nll, functools.partial(self.logits, params),
                        h, 1)
        parts = {XENT: jnp.mean(xent), MTP_XENT: jnp.zeros((), jnp.float32)}
        per_seq = xent
        if m is not None:
            with jax.named_scope("mtp_head"):
                # (``weighted_nll`` without its own ``head`` scope)
                mtp_xent = mean_nll(
                    weighted_nll.__wrapped__,
                    functools.partial(self.mtp_logits, params), m, 2)
            parts[MTP_XENT] = jnp.mean(mtp_xent)
            per_seq = xent + self.mtp_weight * mtp_xent
        if data_axis is not None:
            parts = jax.lax.pmean(parts, data_axis)
        return per_seq, {**counts, **parts}
