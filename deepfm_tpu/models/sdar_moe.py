"""Block-diffusion mixture-of-experts decoder (``--model sdar_moe``).

The first model of the zoo that is no pooled ranker: a pre-norm residual
decoder (RMSNorm; grouped-query attention with per-head QK-norm and rotary
positions; a top-k softmax router over an expert layer) trained as a
*block-diffusion* language model (SDAR, arXiv:2510.06303; mask and objective
of BD3-LM, arXiv:2503.09573). ``benchmark/reference_sdar_moe.py`` holds the
equations; this is the program's form of them.

What it reads of a batch: ``hist_ids`` [B, L], the sequence's tokens, which
ride the record's history list (``--history_max_len L``); ``feat_ids``,
``feat_vals`` and ``label`` are in the record because the codec writes them
and are not read. The token table is the ``EmbeddingSchema`` entry
``tok_emb`` (``--feature_size`` rows of ``--embedding_size``, the model's
width); the last row is ``[MASK]``.

One step: each block of ``--diffusion_block`` tokens draws t ~ U[t_min, 1]
and masks its tokens with probability t (``draw_noise``, from the step's
key); the model reads ``[noisy ; clean]``, 2L positions at position indices
``(0..L-1, 0..L-1)``, under the block-diffusion mask (``allowed``); the loss
is the 1/t-weighted cross-entropy over the masked positions of the noisy
half. The model owns that loss (``owns_loss``): the trainer takes its
per-sequence values where a ranker hands it one logit an example.

**A share of a layer.** The layer is told what it holds: ``--attn_q_heads``
and ``--attn_kv_heads`` are the heads whose projections are here,
``--moe_experts_held`` experts from ``--moe_first_expert`` on of the
``--moe_experts`` the router scores. The router keeps its width and its
``--moe_top_k``; a (position, expert) pair that names an absent expert adds
nothing, and the partial sums of ``wo`` and of the experts go on to the next
layer unreduced: on one chip the layer runs without its exchange, and no
code stands in for the absent chips.

**The expert layer.** The pairs that land on held experts are sorted by expert
into a buffer of ``--moe_pair_capacity`` rows (static shapes; made up to whole
``PRODUCT_TILE_ROWS``, ``pass_rows``), which is computed in equal passes of at
most ``PASS_ROWS`` rows (one pass's memory): the three products run as grouped
products over the held experts', and the rows are weighted and added back to
their positions. The sorted pairs put the held experts' first, so a pass's
real rows are a prefix of it, ``ends[-1]`` rows, and the rest of the buffer
(twice the mean load) is spare. What multiplies a pass's rows and what moves
them from and to their positions are each picked from what the code can see
(``moe_products_by``, ``moe_rows_by``: backend, widths in whole 128-lane
lines, whole tiles of positions and of buffer rows, one device's program; no
flag). On a TPU at such widths the products are the kernels of
``ops/pallas_grouped_dot`` — forward, in the pass's recomputation and in both
gradients a grid over the row tiles the groups really reach, so a pass costs
what its prefix holds and an empty pass next to nothing — and the rows move
by the two kernels of ``ops/pallas_moe_rows``, one DMA a row over the prefix
only: ``gather`` (the rows of RMSNorm(x), cast to the products' type on their
way; in the backward pass their cotangent summed into the positions',
float32, in the pass loop's carry) and ``combine`` (a pass's weighted rows
added into the layer's sum in place, a group's positions at a time; backward,
the sum's cotangent taken to the rows). **The spare rows of a product's
result are then never written: they hold anything, and nothing that sums
reads them** — the row kernels and the gradients' kernels stop at the prefix
or select before they multiply, the XLA row path reads under
``jnp.where(valid, ...)``, and ``silu(gate) * up`` between the products is
elementwise. Everywhere else (a CPU, the tests' narrow rows, a step across
data replicas) the products are ``jax.lax.ragged_dot`` over every row of the
buffer, the spare rows in its last group (their inputs have to be zeros and
not whatever lay there, or a stray NaN times a zero cotangent would reach a
weight's gradient: both row paths write them so), and the rows move by
``jnp.take`` and ``.at[].add`` over every row, spare ones masked. No pair is
dropped silently: pairs beyond the buffer's rows are counted
(``moe_pairs_over_buffer``, cumulative), with the pairs held, the fullest
expert's count, the fullest layer's pairs (what the buffer has to hold) and
the masked positions of the last step; the counts ride the model state and
the step's metrics.

**The masked scores** (``masked_scores``). Between the rotated q/k/v and
``wo`` the block computes softmax(mask(q k^T / sqrt(D))) v in one of two
ways, picked from what the code can see (``attn_scores_by``: backend,
head_dim, the sequence against the kernel's block, one device's program; no
flag). The mask is a ``ScoreMask``: its pair function and the key that names
it, this model's ``block_diffusion(length, block)``, another model's its own
(``models.kimi_linear.causal``, which ``models.solar_open2`` runs through
the same two paths). On a
TPU at a head_dim of whole 128-lane lines it is one Pallas flash-attention
call (``ops/block_attention``, the kernel JAX ships) whose grid visits only
the blocks of the score matrix in which the mask is true anywhere
(80 of 256 blocks of 512 at L = 4,096 under ``allowed_pairs``, 136 of 256
at 8,192 positions under the causal mask): a block's scores live and die in
VMEM, forward and backward, float32 scores, max, sum and accumulators on
operands of the compute precision. Everywhere else (a CPU, the tests' small
shapes, a step across data replicas) it is XLA ops, a chunk of
``QUERY_CHUNK`` queries against every key at a time, the mask applied to
scores computed whole. The mask's pair function (here ``allowed_pairs``) is
the one statement of it both read.

Memory: each layer is recomputed in the backward pass (``jax.checkpoint``
around the scanned layer). No [S, S] score matrix is ever held: the kernel
keeps a block of scores in VMEM and hands its backward kernels, which make
the scores again, its output and a log-sum-exp a query; the XLA path runs a
chunk of queries at a time, each chunk recomputed too, so its [chunk, S]
scores do not outlive the chunk. Where the scores are the kernel's, a layer
keeps those two tensors (``ops/block_attention.KEPT``: 17 MB a layer at 4
held heads of 128 over 2 x 8,192 positions, the scan's stacked outputs) and
is made again around the forward kernel, which so runs once a layer and
step, wherever the device's memory holds them beside the step (``kept_by``:
the device's memory limit, 16 bytes a parameter under Adam, ``KEEP_RESERVE``
a position; every layer or none, the layers are one scan; no flag;
``step_notes``: ``attn_kept``, ``6/6 layers 0.10 GB``; off a TPU, on the XLA
path or where the device says nothing of its memory, ``0/6``).

**The head's loss** (``weighted_nll``, which the other decoders import): the
final norm, the head product and the cross-entropy, ``HEAD_CHUNK`` positions
at a time, so that no [L, V] of logits is held. Where the loss is
differentiated the forward pass makes a chunk's gradient while its logits
are at hand (a ``jax.custom_vjp``: three products a chunk, the logits made
once, the head's and the norm's gradients summed a sequence in float32 and
scaled by the cotangent in the backward pass, which makes no product);
evaluation makes one product a chunk. Where the further copies a sequence do
not fit the device's memory, or the step runs across data replicas
(``head_grad_by``; no flag), each chunk is made again in the backward pass
under ``jax.checkpoint`` (``step_notes``: ``head_grad``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Hashable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops import block_attention, pallas_grouped_dot, pallas_moe_rows
from . import common
from .graph import GraphModel

#: The step's counts, in the model state and (by ``step_counts``) the metrics.
COUNT_NAMES = ("moe_pairs_held", "moe_pairs_over_buffer",
               "moe_expert_load_max", "moe_layer_pairs_max",
               "masked_positions")
#: (position, layer) selections of a step whose ``top_k`` experts are not the
#: ``top_k`` largest unbiased scores: counted where a layer has a selection
#: bias, so that a run says whether the bias decided anything.
BIAS_MOVED = "moe_bias_moved_picks"
#: Queries a chunk of attention scores holds, and positions a chunk of the
#: head's logits (all of them when the sequence does not divide).
QUERY_CHUNK = 1024
HEAD_CHUNK = 1024
#: The share of a device's memory (1 / this) that a head pass's gradients a
#: sequence may take beyond their sum (``head_grad_by``): 1.06 GB of a v5e's
#: 16.91. The fullest of the cells' steps, GLM-4.7-Flash's, holds 0.32 GB
#: more a pass at B = 2 (PERF.md section 6, PR 49).
HEAD_KEPT_DIVISOR = 16
#: Queries by keys a block of the attention kernel holds, forward and
#: backward. On a v5e at the cell's shapes (q [2, 4, 8192, 128] on one
#: key/value head, L = 4,096; PERF.md section 6, PR 32), forward / forward +
#: backward a layer: 512 (80 of 256 blocks visited) 0.93 / 3.33 ms; 1,024
#: with a compute block of 512 (24 of 64) 0.92 / 3.22; 256 2.1 / 7.5 with the
#: mask computed in the kernel, where 512 read 1.47 / 5.12; the chunked XLA
#: path 9.8 / 23.1.
ATTN_BLOCK = 512
#: Most rows of the expert layer's pair buffer computed at once: a pass holds
#: its rows' inputs, both hidden products and the output (15 KB a row at the
#: published widths), and is made again in the backward pass.
PASS_ROWS = 20480
#: Rows a pass is made whole multiples of, where it has that many: the row
#: tile of the grouped products, the kernels' (``pallas_grouped_dot
#: .TILE_ROWS`` divides it) and XLA:TPU's. ``jax.lax.ragged_dot`` over a
#: buffer that is no multiple of 128 rows took 5.7 x as long (3,280 rows
#: against 3,328: 4.4 / 12 ms forward / with both gradients against 0.76 /
#: 2.55), and over a multiple of 128 that is none of 256 about 1.5 x (4,992
#: against 5,120; PERF.md, PR 37). The rows added are spare rows: the kernels
#: never visit them, ``ragged_dot`` pays 0.4 us each.
PRODUCT_TILE_ROWS = 256


def rms_norm(x: jnp.ndarray, gain: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def rotary(x: jnp.ndarray, positions: jnp.ndarray, theta: float
           ) -> jnp.ndarray:
    """Rotate-half rotary embedding of ``x`` [B, S, H, D] (float32) at
    ``positions`` [S]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rot * sin


def allowed_pairs(q, k, length: int, block: int):
    """May the query at index ``q`` read the key at index ``k`` (indices into
    ``[noisy ; clean]``, 2 * ``length`` positions; integer arrays that
    broadcast against each other, NumPy's or ``jnp``'s)? A noisy query reads
    its own block's noisy keys and earlier blocks' clean keys; a clean query
    reads clean keys of its own and earlier blocks. The one statement of the
    block-diffusion mask: the XLA path and the kernel both evaluate it."""
    q_noisy, k_noisy = q < length, k < length
    qb, kb = (q % length) // block, (k % length) // block
    same = kb == qb
    return (k_noisy & q_noisy & same) | (
        ~k_noisy & ((kb < qb) | (~q_noisy & same)))


def allowed(q_index: jnp.ndarray, k_index: jnp.ndarray, length: int,
            block: int) -> jnp.ndarray:
    """bool [Q, K] of ``allowed_pairs``: every query of ``q_index`` against
    every key of ``k_index``."""
    return allowed_pairs(q_index[:, None], k_index[None, :], length, block)


@dataclasses.dataclass(frozen=True)
class ScoreMask:
    """Which keys a query may read, stated once for both score paths:
    ``pairs(q, k) -> bool`` on integer index arrays that broadcast against
    each other (NumPy's or ``jnp``'s), and ``key``, which says which mask it
    is: two masks of one key are the same mask (the kernel's tables are
    built and kept by it, ``attn_kernel``). Called with ``q_index`` [Q] and
    ``k_index`` [K] it is the bool [Q, K] the XLA path applies."""
    key: Hashable
    pairs: Callable = dataclasses.field(compare=False)

    def __call__(self, q_index: jnp.ndarray, k_index: jnp.ndarray):
        return self.pairs(q_index[:, None], k_index[None, :])


def block_diffusion(length: int, block: int) -> ScoreMask:
    """``allowed_pairs`` at ``length`` and ``block`` as a ``ScoreMask``."""
    return ScoreMask(("block_diffusion", length, block), functools.partial(
        allowed_pairs, length=length, block=block))


def draw_noise(key: jax.Array, tokens: jnp.ndarray, *, block: int,
               t_min: float, mask_id: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(noisy tokens [B, L], t [B, L / block]) of one step: a t ~ U[t_min, 1]
    a block, each of its tokens ``mask_id`` with probability t. The one place
    the step's noise is drawn: a check that holds the step's key draws the
    same."""
    b, length = tokens.shape
    k_t, k_mask = jax.random.split(key)
    t = jax.random.uniform(k_t, (b, length // block), jnp.float32,
                           minval=t_min, maxval=1.0)
    u = jax.random.uniform(k_mask, (b, length), jnp.float32)
    masked = u < jnp.repeat(t, block, axis=1)
    return jnp.where(masked, jnp.int32(mask_id), tokens), t


def _operand(x: jnp.ndarray, compute_dtype: jnp.dtype) -> jnp.ndarray:
    """A matrix product's operand: rounded to the compute precision, and
    handed to the MXU in at least bfloat16 (a grouped product takes nothing
    narrower; a precision below that is a rounding of the operands)."""
    x = x.astype(compute_dtype)
    return x if compute_dtype.itemsize >= 2 else x.astype(jnp.bfloat16)


def _operand_bytes(compute_dtype: jnp.dtype) -> int:
    """Bytes a number of an ``_operand``."""
    return max(compute_dtype.itemsize, 2)


def _dot(x: jnp.ndarray, w: jnp.ndarray, cdt: jnp.dtype) -> jnp.ndarray:
    """``x @ w`` with float32 accumulation and result."""
    return jnp.matmul(_operand(x, cdt), _operand(w, cdt),
                      preferred_element_type=jnp.float32)


def _scores_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                cdt: jnp.dtype, mask: ScoreMask) -> jnp.ndarray:
    """softmax(mask(q k^T / sqrt(D))) v as XLA ops, a chunk of queries at a
    time, each chunk made again in the backward pass: q [B, S, n_kv, G, D],
    k [B, S, n_kv, D] and v [B, S, n_kv, Dv] (operands) ->
    [B, S, n_kv * G * Dv] float32. ``mask(q_index [Q], k_index [K])`` says
    which keys a query reads (a ``ScoreMask``: this model's
    ``block_diffusion``, ``models.kimi_linear.causal``)."""
    b, s, n_kv, group, head_dim = q.shape
    chunk = QUERY_CHUNK if s % QUERY_CHUNK == 0 else s
    scale = 1.0 / math.sqrt(head_dim)
    k_index = jnp.arange(s)

    @jax.checkpoint
    def one_chunk(args):
        q_c, start = args                       # [B, Qc, n_kv, G, D]
        scores = jnp.einsum("bqngd,bknd->bngqk", q_c, k,
                            preferred_element_type=jnp.float32) * scale
        ok = mask(start + jnp.arange(chunk), k_index)
        scores = jnp.where(ok[None, None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", _operand(p, cdt), v,
                          preferred_element_type=jnp.float32)

    # Positions ahead of heads, as the projections leave them: with heads
    # ahead ([B, n_kv, G, S, D]) the score fusions ran 45 times slower on
    # the chip (PERF.md, PR 31).
    n_chunks = s // chunk
    q_chunks = jnp.moveaxis(
        q.reshape(b, n_chunks, chunk, n_kv, group, head_dim), 1, 0)
    out = jax.lax.map(one_chunk, (q_chunks, jnp.arange(n_chunks) * chunk))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


@functools.lru_cache(maxsize=None)
def attn_kernel(seq: int, mask: ScoreMask, heads: int,
                interpret: bool = False, kernel_block: int = ATTN_BLOCK):
    """The masked attention kernel of one key/value head's ``heads`` query
    heads over ``seq`` positions under ``mask`` (``ops/block_attention``),
    built once a (mask key, shape) and kept: finding the non-empty blocks
    takes half a second at S = 8,192 and the step is traced more than once a
    run."""
    with jax.ensure_compile_time_eval():
        return block_attention.make_kernel(
            mask.pairs, mask.key, seq=seq, heads=heads, block=kernel_block,
            interpret=interpret)


def _scores_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   mask: ScoreMask, interpret: bool = False,
                   kernel_block: int = ATTN_BLOCK) -> jnp.ndarray:
    """``_scores_xla``'s result under ``mask`` from the kernel, which visits
    only the blocks of the score matrix the mask leaves something in and
    keeps a block's scores in VMEM, forward and backward. q arrives scaled
    by ``1/sqrt(D)``; the result is in the operands' type."""
    b, s, n_kv, group, head_dim = q.shape
    kernel = attn_kernel(s, mask, group, interpret, kernel_block)
    heads_first = jax.vmap(jax.vmap(kernel))(      # over B and n_kv
        jnp.transpose(q, (0, 2, 3, 1, 4)),          # [B, n_kv, G, S, D]
        jnp.transpose(k, (0, 2, 1, 3)), jnp.transpose(v, (0, 2, 1, 3)))
    return jnp.transpose(heads_first, (0, 3, 1, 2, 4)).reshape(b, s, -1)


def attn_notes(scores_by: str, mask: ScoreMask, seq: int, group: int
               ) -> Dict[str, str]:
    """What ``step_notes`` says of the masked scores: ``attn_scores``
    (``kernel`` / ``xla``) and, of the kernel, ``attn_score_blocks`` (blocks
    of the score matrix the forward pass computes / all of them, a head)."""
    if scores_by != "kernel":           # every score of every chunk
        return {"attn_scores": scores_by}
    visited, total = block_attention.visited_blocks(
        attn_kernel(seq, mask, group), seq, ATTN_BLOCK)
    return {"attn_scores": scores_by,
            "attn_score_blocks": f"{visited}/{total}"}


def masks_notes(scores_by: str, masks: Dict[str, ScoreMask], seq: int,
                group: int) -> Dict[str, str]:
    """``attn_notes`` of a stack whose layers run under more than one mask:
    ``masks`` {the note that holds a mask's visited blocks: the mask} ->
    ``attn_scores`` once and, of the kernel, each mask's blocks under its
    own name (``attn_score_blocks`` the causal layers', ``attn_window_blocks``
    the windowed ones')."""
    out = {}
    for key, mask in masks.items():
        notes = attn_notes(scores_by, mask, seq, group)
        out["attn_scores"] = notes["attn_scores"]
        if "attn_score_blocks" in notes:
            out[key] = notes["attn_score_blocks"]
    return out


def attn_scores_by(seq: int, head_dim: int, *, one_device: bool = True,
                   backend: Optional[str] = None) -> str:
    """``kernel`` where the masked attention kernel applies (a TPU
    backend, ``head_dim`` whole 128-lane lines or half lines, a sequence
    its block divides: ``ops/block_attention.supported``; and a step that is
    one device's program: the shipped kernel does not say how its results vary
    over a mesh's axes, which a step across data replicas is checked for),
    else ``xla``: what the compiled step's attention is made of, read from
    the backend, the shapes and the mesh."""
    backend = jax.default_backend() if backend is None else backend
    return ("kernel" if one_device and block_attention.supported(
        backend, seq, head_dim, ATTN_BLOCK) else "xla")


def masked_scores(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  mask: ScoreMask, cdt: jnp.dtype, scores_by: str = "xla"
                  ) -> jnp.ndarray:
    """softmax(mask(q k^T / sqrt(D))) v by the path ``scores_by`` names
    (``attn_scores_by``'s word): q [B, S, Hq, D] float32, rounded here once;
    k, v [B, S, n_kv, D] operands; query head j reads key/value head
    j // (Hq / n_kv) -> [B, S, Hq * D]."""
    b, s, n_q, head_dim = q.shape
    n_kv = k.shape[2]
    grouped = (b, s, n_kv, n_q // n_kv, head_dim)
    if scores_by == "kernel":
        # the kernel applies no scale: it goes into q ahead of q's one
        # rounding to the compute precision
        q = _operand(q * (1.0 / math.sqrt(head_dim)), cdt)
        return _scores_kernel(q.reshape(grouped), k, v, mask=mask)
    return _scores_xla(_operand(q, cdt).reshape(grouped), k, v, cdt=cdt,
                       mask=mask)


@jax.named_scope("attn")
def attention(lp: Dict[str, jnp.ndarray], x: jnp.ndarray,
              positions: jnp.ndarray, *, mask: ScoreMask,
              head_dim: int, eps: float, theta: Optional[float],
              cdt: jnp.dtype, scores_by: str = "xla",
              scores_scope: Optional[str] = None) -> jnp.ndarray:
    """The held heads' part of ``Attn(RMSNorm(x))``: x [B, S, d] -> [B, S, d]
    (``wo``'s sum over the held heads, unreduced): grouped-query attention
    under ``mask`` (this model's ``block_diffusion``, ``models.lfm2_moe``'s
    ``kimi_linear.causal``, ``models.afmoe``'s ``kimi_linear.window``), each
    further step there where the layer has it: a per-head RMS norm of q and
    k where the layer has the gains (``q_norm``, ``k_norm``); rotary
    positions where the layer rotates (``theta`` None: a positionless
    layer); the heads' outputs times ``sigmoid(xn wg)`` ahead of ``wo``
    where the layer has the gate (``wg`` [d, Hq * D]). This model and
    ``lfm2_moe`` norm and rotate, ``solar_open2`` gates only, ``afmoe``
    norms and gates every layer and rotates its windowed ones. ``scores_by``
    is ``attn_scores_by``'s word for what makes the masked scores;
    ``scores_scope`` names a scope of their own for them inside ``attn`` (a
    model whose metrics read the scores apart)."""
    b, s, _ = x.shape
    xn = rms_norm(x, lp["norm1"], eps)
    q = _dot(xn, lp["wq"], cdt).reshape(b, s, -1, head_dim)
    k = _dot(xn, lp["wk"], cdt).reshape(b, s, -1, head_dim)
    v = _dot(xn, lp["wv"], cdt).reshape(b, s, -1, head_dim)

    def normed_rotated(y, gain):
        if gain in lp:
            y = rms_norm(y, lp[gain], eps)
        return y if theta is None else rotary(y, positions, theta)

    q, k = normed_rotated(q, "q_norm"), normed_rotated(k, "k_norm")
    with (jax.named_scope(scores_scope) if scores_scope
          else contextlib.nullcontext()):
        out = masked_scores(q, _operand(k, cdt), _operand(v, cdt),
                            mask=mask, cdt=cdt, scores_by=scores_by)
    if "wg" in lp:
        out = out * jax.nn.sigmoid(_dot(xn, lp["wg"], cdt))
    return _dot(out, lp["wo"], cdt)


def route(xn: jnp.ndarray, router: jnp.ndarray, top_k: int, *,
          score: Callable = functools.partial(jax.nn.softmax, axis=-1),
          bias: Optional[jnp.ndarray] = None, scale: float = 1.0,
          renorm_eps: float = 0.0
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """xn [T, d] -> (experts [T, k], weights [T, k], moved []): the k largest
    of a float32 ``score`` of every expert's logit (a softmax over the
    experts unless the model hands in another: ``jax.nn.sigmoid``), their
    scores renormalised (over their sum + ``renorm_eps``, where a model's
    published form has one) and times ``scale``. ``bias`` [E] is added to the
    scores for the selection only: it moves which experts are the k, never
    their weights; ``moved`` counts the positions where it did (an expert
    left out scores above one taken; none without a bias). Equal scores go
    to the lower index. The product is float32 at full precision: it is 1/40
    of a layer's work, and a rounded logit moves which expert is the k-th."""
    logits = jnp.matmul(xn.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = score(logits)
    if bias is None:
        top_p, top_e = jax.lax.top_k(scores, top_k)
        moved = jnp.zeros((), jnp.int32)
    else:
        _, top_e = jax.lax.top_k(scores + bias, top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        # scores above the lowest taken: more among all than among the taken
        lowest = jnp.min(top_p, axis=-1, keepdims=True)
        moved = jnp.sum(jnp.sum(scores > lowest, axis=-1)
                        > jnp.sum(top_p > lowest, axis=-1), dtype=jnp.int32)
    total = jnp.sum(top_p, axis=-1, keepdims=True)
    weights = top_p / (total + renorm_eps if renorm_eps else total)
    return top_e, (weights if scale == 1.0 else weights * scale), moved


def pass_rows(capacity: int) -> Tuple[int, int]:
    """(passes, rows a pass) of a pair buffer of at least ``capacity`` rows:
    equal passes of at most ``PASS_ROWS``, a pass of ``PRODUCT_TILE_ROWS`` or
    more made up to whole tiles (a smaller buffer is held row for row)."""
    passes = -(-capacity // PASS_ROWS)
    rows = -(-capacity // passes)
    if rows >= PRODUCT_TILE_ROWS:
        rows = -(-rows // PRODUCT_TILE_ROWS) * PRODUCT_TILE_ROWS
    return passes, rows


def moe_rows_by(width: int, positions: int, capacity: int, *,
                one_device: bool = True, backend: Optional[str] = None
                ) -> str:
    """``kernel`` where the expert layer's rows go to and from their
    positions by the row-copy kernels (``ops/pallas_moe_rows.supported``: a
    TPU backend, a row of whole 128-lane lines, positions and a pass in
    whole sublane tiles; and a step that is one device's program,
    as ``attn_scores_by`` asks), else ``xla`` (``jnp.take`` and
    ``.at[].add`` over every row of the buffer): read from the backend, the
    shapes and the mesh."""
    return ("kernel" if one_device and pallas_moe_rows.supported(
        width, positions, pass_rows(capacity)[1], backend) else "xla")


def moe_products_by(width: int, hidden: int, capacity: int, *,
                    one_device: bool = True, backend: Optional[str] = None
                    ) -> str:
    """``kernel`` where a pass's grouped products are the kernels of
    ``ops/pallas_grouped_dot``, which visit the valid prefix's row tiles
    only (``supported``: a TPU backend, the model's and the experts' widths
    in whole 128-lane lines, a pass in whole row tiles; and a step that is
    one device's program, as ``attn_scores_by`` asks), else ``xla``
    (``jax.lax.ragged_dot`` over every row of the buffer, the spare rows in
    the last group): read from the backend, the shapes and the mesh."""
    return ("kernel" if one_device and pallas_grouped_dot.supported(
        pass_rows(capacity)[1], width, hidden, backend) else "xla")


def moe_notes(rows_by: str, capacity: int, layers: int,
              products_by: str = "xla", width: int = 0, hidden: int = 0
              ) -> Dict[str, str]:
    """What ``step_notes`` says of the expert layers: ``moe_rows``
    (``kernel`` / ``xla``: what moves the rows), ``moe_products`` (``kernel
    <tiling>`` / ``xla``: what multiplies them, ``moe_products_by``'s word
    and the kernels' tiles at the experts' ``width`` and ``hidden``) and
    ``moe_rows_moved``, the last step's held pairs over the buffers' rows,
    the count filled in where the notes are written: the rows a row kernel
    moves (XLA's ops move them all) and, to a row tile a group, the share of
    the buffer the product kernels visit (``ragged_dot`` visits it all)."""
    passes, rows = pass_rows(capacity)
    return {"moe_rows": rows_by,
            "moe_products": products_by if products_by != "kernel" else
            "kernel " + pallas_grouped_dot.tiling(width, hidden),
            "moe_rows_moved": "{moe_pairs_held}/%d" % (layers * passes * rows)}


@jax.named_scope("moe")
def expert_layer(lp: Dict[str, jnp.ndarray], x: jnp.ndarray, *,
                 top_k: int, first_expert: int, capacity: int,
                 eps: float, cdt: jnp.dtype, route_by: Callable = route,
                 rows_by: str = "xla", products_by: str = "xla"
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The held experts' part of ``MoE(RMSNorm(x))``: x [B, S, d] ->
    ([B, S, d], counts). ``lp['w_gate']`` [held, d, f] says how many experts
    are held; they are experts ``first_expert`` onwards. The first
    ``capacity`` of the sorted pairs (``pass_rows``: at least that many) are
    computed, in equal passes of at most ``PASS_ROWS`` rows, each made again
    in the backward pass, so that the layer's memory is one pass's; pairs
    beyond them are counted and add nothing. ``route_by(xn, router, top_k,
    bias=)`` is the model's router (``route``, with what the model binds of its
    keywords); ``rows_by`` is ``moe_rows_by``'s word for what moves a pass's
    rows from and to their positions, ``products_by`` ``moe_products_by``'s
    for what multiplies them. Where the layer has a selection bias
    (``lp['select_bias']`` [experts]: ``route``'s ``bias``) the router picks
    by score + bias, and the layer's counts say at how many positions the bias
    moved the picks (``BIAS_MOVED``)."""
    shape = x.shape
    xn = rms_norm(x, lp["norm2"], eps).reshape(-1, shape[-1])
    n_tok = xn.shape[0]
    n_held = lp["w_gate"].shape[0]
    bias = lp.get("select_bias")
    top_e, top_w, moved = route_by(xn, lp["router"], top_k, bias=bias)
    # Sort the (position, expert) pairs by held expert; absent ones last.
    local = top_e.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    # each held expert's pairs: where its key starts in the sorted list
    load = jnp.diff(jnp.searchsorted(
        sorted_key, jnp.arange(n_held + 1, dtype=key.dtype))).astype(
            jnp.int32)
    held = jnp.sum(load)
    passes, buffer_rows = pass_rows(capacity)
    capacity = passes * buffer_rows
    spare = max(capacity - order.shape[0], 0)      # rows past every pair
    order = jnp.concatenate([order, jnp.zeros((spare,), order.dtype)])
    sorted_key = jnp.concatenate(
        [sorted_key, jnp.full((spare,), n_held, key.dtype)])
    pair_weight = top_w.reshape(-1)
    weights = {n: _operand(lp[n], cdt) for n in ("w_gate", "w_up", "w_down")}
    by_kernel = rows_by == "kernel"
    # (a pass's rows leave the kernel in the products' type where that is
    # one the kernel writes)
    taken = cdt if cdt.itemsize >= 2 else jnp.dtype(jnp.float32)

    @jax.checkpoint
    def one_pass(carry, start):
        rows = jax.lax.dynamic_slice(order, (start,), (buffer_rows,))
        valid = jax.lax.dynamic_slice(sorted_key, (start,),
                                      (buffer_rows,)) < n_held
        # Where each held expert's rows end in this pass: its load, cut to
        # the pass. The rows from ends[-1] on name no pair.
        ends = jnp.clip(jnp.cumsum(load) - start, 0, buffer_rows)
        tok = rows // top_k
        if by_kernel:       # the valid prefix's rows; the others zeros
            out, xn_through = carry
            xs, xn_through = pallas_moe_rows.gather(
                jax.lax.stop_gradient(xn), xn_through, tok, ends, taken)
        else:
            out = carry
            xs = jnp.where(valid[:, None], jnp.take(xn, tok, axis=0), 0.0)
        xs = _operand(xs, cdt)

        if products_by == "kernel":     # the prefix's row tiles only: the
            def grouped(a, w):          # other rows hold anything
                return pallas_grouped_dot.grouped_dot(a, w, ends)
        else:       # every row: the spare ones, zeros, ride the last group
            sizes = jnp.diff(ends, prepend=0)
            sizes = sizes.at[-1].add(buffer_rows - ends[-1])

            def grouped(a, w):
                return jax.lax.ragged_dot(
                    a, w, sizes, preferred_element_type=jnp.float32)

        mid = jax.nn.silu(grouped(xs, weights["w_gate"])) \
            * grouped(xs, weights["w_up"])
        y = grouped(_operand(mid, cdt), weights["w_down"])      # [C, d]
        weight = jnp.where(valid, pair_weight[rows], 0.0)
        if by_kernel:       # (weighted inside, the prefix's rows only)
            return (pallas_moe_rows.combine(out, y, weight, tok, ends),
                    xn_through), None
        return out.at[tok].add(jnp.where(valid[:, None], y, 0.0)
                               * weight[:, None]), None

    # (zeros made of xn: across data replicas the carry varies as xn does;
    # by the kernels xn rides along: its cotangent is summed in the carry's)
    out, _ = jax.lax.scan(
        one_pass, (jnp.zeros_like(xn), xn) if by_kernel else xn * 0.0,
        jnp.arange(passes, dtype=jnp.int32) * buffer_rows)
    if by_kernel:
        out = out[0]
    counts = {"moe_pairs_held": held,
              "moe_pairs_over_buffer": jnp.maximum(held - capacity, 0),
              "moe_expert_load_max": jnp.max(load),
              "moe_layer_pairs_max": held,
              **({} if bias is None else {BIAS_MOVED: moved})}
    return out.reshape(shape), counts


def device_memory_bytes() -> int:
    """The memory limit of the device a step is traced for, as the backend
    says it (``memory_stats()["bytes_limit"]``); 0 off a TPU and where the
    device says nothing of its memory."""
    if jax.default_backend() != "tpu":
        return 0
    return int((jax.local_devices()[0].memory_stats() or {}).get(
        "bytes_limit", 0))


#: Parameter-shaped arrays that ``train.optimizers.build_optimizer``'s state
#: holds beside each parameter (a test holds this to the states themselves).
OPTIMIZER_COPIES = {"adam": 2, "ftrl": 2, "adagrad": 1, "momentum": 1,
                    "sgd": 0}
#: Bytes a position of the step that ``kept_by`` leaves free of what layers
#: keep for the backward pass: the room of a layer's working set, which
#: follows the step's positions. Set from the ``models.kimi_linear`` stacks'
#: steps compiled for a v5e (``memory_analysis()``, PERF.md section 6, PR
#: 54; 16,909,336,064 bytes of memory): what a step that keeps nothing holds
#: beyond 16 bytes a parameter is 0.36 GB (Solar-Open2) and 1.00 GB
#: (Phi-4-flash) at 8,192 positions, 1.41 GB (Kimi-Linear), 1.45 GB
#: (GLM-4.7-Flash), 1.95 GB (LFM2) and 2.83 GB (Trinity-Mini) at 16,384: 44
#: to 173 KB a position. 225 KiB is a third above the largest, and inside
#: the window three of those steps leave.
#: Trinity-Mini keeps its five layers' kernel tensors and its four shared
#: experts' products (15.06 GB compiled) from 215 KiB; below, its dense
#: layer's products too, 15.87 GB, over the 15.5 GB its compile test allows
#: a step. GLM-4.7-Flash keeps its six blocks' kernel tensors and five
#: shared experts' products (14.02 GB) up to 227 KiB, and Phi-4-flash's six
#: layers their three kernel tensors and five of six products (14.56 GB) up
#: to 255 KiB; the same stack of eight layers (14.65 GB of parameters,
#: moments and gradients), which compiled before anything was kept and must
#: go on compiling, keeps four layers' kernel tensors and no product: 15.13
#: GB. (200 KiB until PR 54, from PR 47's four steps, 126 to 153 KB a
#: position; Trinity-Mini's came with PR 53. ``SdarMoE``'s scanned stack
#: holds 330 KB a position beyond its parameters' 16 bytes and 2.6 GB under
#: the limit: what its layers keep, 0.10 GB, this reserve does not decide.)
KEEP_RESERVE = 225 * 1024


def held_bytes(params: Any, optimizer: str, head_passes: Sequence[
        Tuple[str, int]], batch: int) -> int:
    """What a train step holds on a device whatever its layers keep, from
    what a model's ``hidden`` can see: the parameters as it is handed them,
    as many more copies as the optimizer's state holds
    (``OPTIMIZER_COPIES``), one of gradients, and what the head passes
    (``head_grad_by``'s word, the float32 bytes the pass reads) keep a
    sequence of ``batch`` beyond that one. (Not the bytes resident when the
    step is traced: ``Trainer.step_compiled`` traces from shapes, and a step
    traced twice has to be one program.)"""
    leaves = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    heads = sum((batch - 1) * size for by, size in head_passes
                if by == "forward")
    return heads + leaves * (2 + OPTIMIZER_COPIES[optimizer.lower()])


def kept_by(layer_bytes: Sequence[int], *, positions: int, limit: int,
            held: int) -> int:
    """How many of a stack's layers keep something for the backward pass (a
    dense SwiGLU's first products, the attention kernel's output and
    log-sum-exp), counted from the last layer back (the last layer's are
    freed first, while the layers' gradients come to life): ``layer_bytes``
    what each would keep, first layer first; ``limit`` the device's memory
    (``device_memory_bytes``; 0: nothing is known, nothing is kept);
    ``held`` what the step holds without them (``held_bytes``, and what was
    placed ahead of these); ``positions`` the step's. A layer keeps while
    what is left of the memory stays above ``KEEP_RESERVE`` bytes a
    position, the room of a layer's working set."""
    if limit <= 0:
        return 0
    room = limit - held - KEEP_RESERVE * positions
    kept = 0
    for size in reversed(layer_bytes):
        if not 0 < size <= room:
            break
        room -= size
        kept += 1
    return kept


def kept_note(kept: int, layers: int, kept_bytes: int) -> str:
    """What ``step_notes`` says of what ``kept`` of ``layers`` layers keep
    (``mlp_kept``, ``attn_kept``)."""
    return f"{kept}/{layers}" + (
        f" layers {kept_bytes / 1e9:.2f} GB" if kept else "")


def layer_policy(keeps: Dict[str, bool]):
    """The ``jax.checkpoint`` policy of a layer that keeps what carries a
    name (``checkpoint_name``) that ``keeps`` says yes to and makes
    everything else again; None, a layer that keeps nothing, where it says
    yes to none."""
    names = [name for name, keep in keeps.items() if keep]
    return (jax.checkpoint_policies.save_only_these_names(*names)
            if names else None)


def head_grad_by(batch: int, param_bytes: int, limit: int, *,
                 one_device: bool = True) -> str:
    """``forward`` where a differentiated ``weighted_nll`` makes a chunk's
    gradient beside its loss, ``recomputed`` where it makes the chunk again
    in the backward pass: read from the shapes, the device's memory
    (``device_memory_bytes``; 0, nothing known of it: ``forward``) and the
    mesh. The forward form holds the gradient of what ``logits_of`` reads
    (``param_bytes``: final norm and head matrix, as float32) a sequence,
    ``batch - 1`` copies more than the sum the other form ends in; it runs
    where those are no more than 1 / ``HEAD_KEPT_DIVISOR`` of the memory
    and the step is one device's program (across data replicas what
    ``logits_of`` closed over is traced as every replica's: its gradient a
    chunk would be summed over them a chunk)."""
    extra = (batch - 1) * param_bytes
    return ("forward" if one_device and (
        limit <= 0 or extra * HEAD_KEPT_DIVISOR <= limit) else "recomputed")


def head_grad_note(by: str, batch: int, param_bytes: int) -> str:
    """What ``step_notes`` says of a head pass (``head_grad``): its form
    (``head_grad_by``'s word) and what the forward form keeps."""
    if by != "forward":
        return by
    return "forward 3 products/chunk, %.2f GB kept" % (
        batch * param_bytes / 1e9)


def _float32_bytes(tree: Any) -> int:
    return sum(4 * x.size for x in jax.tree.leaves(tree))


def _chunked(x: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """[B, L, ...] -> [L / chunk, B, chunk, ...]"""
    b, length = x.shape[:2]
    return jnp.moveaxis(
        x.reshape(b, length // chunk, chunk, *x.shape[2:]), 1, 0)


def _nll(logits: jnp.ndarray, tokens: jnp.ndarray
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(the negative log-likelihood of ``tokens`` [...] under ``logits``
    [..., V], the log of the sum of the logits' exponentials [..., 1]):
    ``jax.nn.log_softmax``'s operations to the bit, the label's column
    taken before the subtractions, so that nothing [..., V] but the logits
    is written out."""
    top = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True))
    at_label = jnp.take_along_axis(logits, tokens[..., None], axis=-1) - top
    return -(at_label - lse)[..., 0], top + lse


def _sums_by_chunk(logits_of: Callable, chunk: int, h: jnp.ndarray,
                   labels: jnp.ndarray, weight: jnp.ndarray, *,
                   remade: bool = False) -> jnp.ndarray:
    """``weighted_nll`` as one product a chunk; ``remade``: each chunk made
    again in the backward pass (``jax.checkpoint``)."""
    def one_chunk(args):
        h_c, tok_c, w_c = args
        return jnp.sum(_nll(logits_of(h_c), tok_c)[0] * w_c, axis=1)

    return jnp.sum(jax.lax.map(
        jax.checkpoint(one_chunk) if remade else one_chunk,
        tuple(_chunked(x, chunk) for x in (h, labels, weight))), axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _nll_sums(logits_fn: Callable, chunk: int, h: jnp.ndarray, consts: Any,
              labels: jnp.ndarray, weight: jnp.ndarray) -> jnp.ndarray:
    """``weighted_nll`` with what ``logits_of`` closed over as an argument
    (``logits_fn(h, *consts)``, traced for one sequence's chunk
    [1, chunk, d]). Not differentiated: one product a chunk, nothing
    kept."""
    def logits_of(h_c):                         # any number of sequences
        return jax.vmap(lambda h_1: logits_fn(h_1[None], *consts)[0])(h_c)

    return _sums_by_chunk(logits_of, chunk, h, labels, weight)


def _nll_sums_fwd(logits_fn, chunk, h, consts, labels, weight):
    """The sums, and each sequence's sum's gradient while a chunk's logits
    are at hand: softmax less the label's one, times the weight, back
    through ``logits_fn`` (``jax.vjp``: whatever it does to what it reads)
    into the chunk's rows of the stream's gradient and into a float32 sum
    of every constant's, carried over the sequence's chunks. Three products
    a chunk, the logits made once; a sequence at a time, so that each
    product is a plain one (XLA:TPU computes a product batched over two
    sequences as a dilated convolution, at twice the work: PERF.md section
    6, PR 49)."""
    def one_chunk(kept, args):
        h_c, tok_c, w_c = args                  # [chunk, d], [chunk] x 2
        logits, pull = jax.vjp(
            lambda h_c, consts: logits_fn(h_c[None], *consts)[0], h_c, consts)
        nll, log_sum = _nll(logits, tok_c)
        at_label = jax.nn.one_hot(tok_c, logits.shape[-1],
                                  dtype=logits.dtype)
        dh, dconsts = pull(
            w_c[:, None] * (jnp.exp(logits - log_sum) - at_label))
        return ([k + g.astype(k.dtype) for k, g in zip(kept, dconsts)],
                (jnp.sum(nll * w_c), nll, dh))

    def one_sequence(kept, args):
        b, *sequence = args
        summed, (sums, nll, dh) = jax.lax.scan(
            one_chunk, [jnp.zeros(c.shape, jnp.float32) for c in consts],
            tuple(x.reshape(-1, chunk, *x.shape[1:]) for x in sequence))
        # (the sequences' sums are the carry, not the loop's stacked
        # results, and start as zeros made of h: XLA fills a loop's results,
        # and any constant, under no scope)
        kept = [jax.lax.dynamic_update_index_in_dim(k, g, b, 0)
                for k, g in zip(kept, summed)]
        return kept, (jnp.sum(sums), nll.reshape(-1),
                      dh.reshape(sequence[0].shape))

    batch = h.shape[0]
    zero = h[:, 0, 0].astype(jnp.float32) * 0.0
    kept, (sums, nll, dh) = jax.lax.scan(
        one_sequence,
        [jnp.broadcast_to(zero.reshape(batch, *[1] * c.ndim),
                          (batch, *c.shape)) for c in consts],
        (jnp.arange(batch), h, labels, weight))
    return sums, (nll, dh, kept, consts)


def _nll_sums_bwd(logits_fn, chunk, kept, g):
    """The kept gradients times the sums' cotangent [B]: no product. (Its
    ops carry the scope ``weighted_nll`` was called under.)"""
    nll, dh, dconsts, consts = kept
    return (g[:, None, None] * dh,
            [jnp.sum(g.reshape(-1, *[1] * c.ndim) * k, axis=0).astype(c.dtype)
             for k, c in zip(dconsts, consts)],
            None, g[:, None] * nll)


_nll_sums.defvjp(_nll_sums_fwd, _nll_sums_bwd)


@jax.named_scope("head")
def weighted_nll(logits_of: Callable, h: jnp.ndarray, labels: jnp.ndarray,
                 weight: jnp.ndarray) -> jnp.ndarray:
    """sum over a sequence's positions of ``weight`` times the negative
    log-likelihood of ``labels`` [B, L] under ``logits_of(h)`` (h [B, L, d]
    -> [B, L, V]; it may close over traced parameters) -> [B], a chunk of
    ``HEAD_CHUNK`` positions at a time; a chunk's logits are never all held.
    Where the sums are differentiated the forward pass makes a chunk's
    gradient while its logits are at hand (``_nll_sums_fwd``: three products
    a chunk) and the backward pass makes no product; where a sequence's
    copy of the head's gradients does not fit (``head_grad_by``) each chunk
    is made again in the backward pass (``jax.checkpoint``: four products
    and more). Not differentiated it is one product a chunk."""
    b, length, _ = h.shape
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length
    logits_fn, consts = jax.closure_convert(logits_of, h[:1, :chunk])
    if head_grad_by(b, _float32_bytes(consts), device_memory_bytes(),
                    one_device=not jax.typeof(h).vma) == "forward":
        return _nll_sums(logits_fn, chunk, h, consts, labels, weight)
    return _sums_by_chunk(logits_of, chunk, h, labels, weight, remade=True)


class SdarMoE(GraphModel):
    """Block-diffusion MoE decoder over ``hist_ids``; see the module's
    docstring."""

    name = "sdar_moe"
    #: the trainer forwards hist_ids (the tokens) when the batch has them
    uses_history = True
    #: the model computes its own per-example loss (``per_example_loss``)
    owns_loss = True
    #: Where the expert layer's products are ``ragged_dot``
    #: (``moe_products_by``: a TPU step across data replicas, or at widths
    #: of no whole lines), XLA's TPU backend compiles it to kernels it
    #: names ``ragged-dot-*`` and strips of where they were traced: the
    #: step's text puts them under ``moe`` (``Trainer.step_hlo_text``). The
    #: kernels of ``ops/pallas_grouped_dot`` keep their own ``op_name``.
    kernel_scopes = (("ragged-dot", "moe"),)

    def __init__(self, cfg: Any):
        super().__init__(cfg)
        self.cdt = jnp.dtype(cfg.compute_dtype)
        self.mask_id = int(cfg.feature_size) - 1
        #: What the traced step is made of, said beside its counts on
        #: ``train.log_sync`` while tracing is on: ``attn_scores`` (``kernel``
        #: / ``xla``) and, of the kernel, ``attn_score_blocks`` (blocks of
        #: the score matrix the forward pass computes / all of them, a head);
        #: ``attn_kept`` (``kept_note``: the layers that keep the kernel's
        #: output and log-sum-exp for the backward pass); ``moe_rows``,
        #: ``moe_products`` and ``moe_rows_moved`` (``moe_notes``);
        #: ``head_grad`` (``head_grad_note``). A note may name a count of
        #: the step in braces.
        self.step_notes: Dict[str, str] = {}

    def _attn_notes(self, scores_by: str, seq: int, length: int
                    ) -> Dict[str, str]:
        return attn_notes(
            scores_by, block_diffusion(length, self.cfg.diffusion_block),
            seq, self.cfg.attn_q_heads // self.cfg.attn_kv_heads)

    def _head_grad(self, params: common.Params, batch: int,
                   one_device: bool) -> Tuple[str, int]:
        """(``head_grad_by``'s word, the float32 bytes of what the pass
        reads) of the head pass of a step of ``batch`` sequences."""
        read = _float32_bytes((params["final_norm"], params["head"]))
        return head_grad_by(batch, read, device_memory_bytes(),
                            one_device=one_device), read

    def _attn_keep(self, params: common.Params, ids: jnp.ndarray,
                   scores_by: str) -> bool:
        """Whether the layers of the step of ``ids`` [B, 2L] keep the
        attention kernel's output and log-sum-exp for the backward pass
        (``kept_by``: every layer or none, the layers are one scan; nothing
        where the scores are not the kernel's); ``step_notes`` is told."""
        cfg = self.cfg
        layers = cfg.decoder_layers
        size = block_attention.kept_bytes(
            ids.size * cfg.attn_q_heads, cfg.attn_head_dim,
            _operand_bytes(self.cdt))
        keep = scores_by == "kernel" and layers == kept_by(
            [size] * layers, positions=ids.size, limit=device_memory_bytes(),
            held=held_bytes(params, cfg.optimizer,
                            [self._head_grad(params, ids.shape[0], True)],
                            ids.shape[0]))
        self.step_notes["attn_kept"] = kept_note(
            layers * keep, layers, layers * size * keep)
        return keep

    def embedding_param_names(self) -> Tuple[str, ...]:
        return ("tok_emb",)

    def init_counts(self) -> common.State:
        return {n: jnp.zeros((), jnp.int32) for n in COUNT_NAMES}

    def step_counts(self, model_state: common.State
                    ) -> Dict[str, jnp.ndarray]:
        """The counts a step's metrics carry beside its loss."""
        return dict(model_state)

    def init(self, rng: jax.Array) -> Tuple[common.Params, common.State]:
        cfg = self.cfg
        d, hd, f = cfg.embedding_size, cfg.attn_head_dim, cfg.moe_expert_width
        n, held = cfg.decoder_layers, cfg.moe_experts_held
        keys = iter(jax.random.split(rng, 12))

        def glorot(*shape):
            return common.glorot_uniform(next(keys), (n, *shape))

        layers = {
            "norm1": jnp.ones((n, d), jnp.float32),
            "wq": glorot(d, cfg.attn_q_heads * hd),
            "wk": glorot(d, cfg.attn_kv_heads * hd),
            "wv": glorot(d, cfg.attn_kv_heads * hd),
            "q_norm": jnp.ones((n, hd), jnp.float32),
            "k_norm": jnp.ones((n, hd), jnp.float32),
            "wo": glorot(cfg.attn_q_heads * hd, d),
            "norm2": jnp.ones((n, d), jnp.float32),
            "router": glorot(d, cfg.moe_experts),
            "w_gate": glorot(held, d, f),
            "w_up": glorot(held, d, f),
            "w_down": glorot(held, f, d),
        }
        params = {
            "tok_emb": self.emb.init_entry(next(keys), (d,)),
            "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "head": common.glorot_uniform(next(keys), (d, cfg.feature_size)),
        }
        return params, self.init_counts()

    def hidden(self, params: common.Params, ids: jnp.ndarray, *,
               shard_axis: Optional[str] = None,
               emb_rows: Optional[Dict[str, Any]] = None,
               emb_plan: Optional[Dict[str, Any]] = None,
               data_axis: Optional[str] = None,
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """ids [B, 2L] (``[noisy ; clean]``) -> (the last residual stream
        [B, 2L, d], the layers' counts summed; the fullest expert's the
        largest). ``data_axis`` names the mesh axis of a step across data
        replicas (the counts are this replica's all the same)."""
        cfg = self.cfg
        seq = ids.shape[1]
        length = seq // 2
        positions = jnp.arange(seq) % length
        scores_by = attn_scores_by(seq, cfg.attn_head_dim,
                                   one_device=data_axis is None)
        rows_by = moe_rows_by(cfg.embedding_size, ids.size,
                              cfg.moe_pair_capacity,
                              one_device=data_axis is None)
        products_by = moe_products_by(
            cfg.embedding_size, cfg.moe_expert_width, cfg.moe_pair_capacity,
            one_device=data_axis is None)
        mask = block_diffusion(length, cfg.diffusion_block)
        self.step_notes = {
            **self._attn_notes(scores_by, seq, length),
            **moe_notes(rows_by, cfg.moe_pair_capacity, cfg.decoder_layers,
                        products_by, cfg.embedding_size,
                        cfg.moe_expert_width)}
        # (layers by the kernel keep its output and log-sum-exp: a layer is
        # made again around the forward kernel, not through it)
        policy = layer_policy(
            {block_attention.KEPT: self._attn_keep(params, ids, scores_by)})
        x = self._emb_lookup(params, "tok_emb", ids, shard_axis, emb_rows,
                             emb_plan).astype(jnp.float32)

        @functools.partial(jax.checkpoint, policy=policy)
        def layer(x, lp):
            # The barrier keeps the layer's casts to the compute precision
            # inside the loop: without it XLA casts the whole stack of every
            # layer's weights ahead of the loop (1.1 GB here that a step
            # does not have to spare).
            lp = jax.lax.optimization_barrier(lp)
            h = x + attention(
                lp, x, positions, mask=mask,
                head_dim=cfg.attn_head_dim, eps=cfg.rms_norm_eps,
                theta=cfg.rope_theta, cdt=self.cdt, scores_by=scores_by)
            y, counts = expert_layer(
                lp, h, top_k=cfg.moe_top_k,
                first_expert=cfg.moe_first_expert,
                capacity=cfg.moe_pair_capacity,
                eps=cfg.rms_norm_eps, cdt=self.cdt, rows_by=rows_by,
                products_by=products_by)
            return h + y, counts

        x, counts = jax.lax.scan(layer, x, params["layers"])
        return x, {k: (jnp.max(v) if k.endswith("_max") else jnp.sum(v))
                   for k, v in counts.items()}

    @jax.named_scope("head")
    def logits(self, params: common.Params, h: jnp.ndarray) -> jnp.ndarray:
        """[..., d] of the last residual stream -> [..., V]: final norm and
        head product."""
        hn = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return _dot(hn, params["head"], self.cdt)

    @jax.named_scope("head")
    def _head_loss(self, params: common.Params, h: jnp.ndarray,
                   tokens: jnp.ndarray, masked: jnp.ndarray,
                   t: jnp.ndarray) -> jnp.ndarray:
        """Loss a sequence [B] from the noisy half's residual stream h
        [B, L, d]: the 1/t-weighted cross-entropy at the masked positions
        over L (``weighted_nll``)."""
        weight = jnp.where(masked, 1.0 / jnp.repeat(
            t, self.cfg.diffusion_block, axis=1), 0.0)
        by, read = self._head_grad(params, h.shape[0],
                                   not jax.typeof(h).vma)
        self.step_notes["head_grad"] = head_grad_note(by, h.shape[0], read)
        return weighted_nll(functools.partial(self.logits, params), h,
                            tokens, weight) / h.shape[1]

    def _run(self, params, state, tokens, rng, shard_axis, data_axis, emb):
        cfg = self.cfg
        tokens = tokens.astype(jnp.int32)
        if rng is None:         # eval: one fixed draw, the same every call
            rng = jax.random.PRNGKey(0)
        noisy, t = draw_noise(rng, tokens, block=cfg.diffusion_block,
                              t_min=cfg.diffusion_t_min,
                              mask_id=self.mask_id)
        h, counts = self.hidden(params, jnp.concatenate([noisy, tokens], 1),
                                shard_axis=shard_axis, data_axis=data_axis,
                                **emb)
        masked = noisy != tokens
        h = h[:, : tokens.shape[1]]
        per_seq = self._head_loss(params, h, tokens, masked, t)
        counts["masked_positions"] = jnp.sum(masked, dtype=jnp.int32)
        if data_axis is not None:       # the replicas' counts, as one
            counts = {k: (jax.lax.pmax(v, data_axis) if k.endswith("_max")
                          else jax.lax.psum(v, data_axis))
                      for k, v in counts.items()}
        counts["moe_pairs_over_buffer"] = (
            state["moe_pairs_over_buffer"] + counts["moe_pairs_over_buffer"])
        return h, per_seq, counts

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
        """Logits [B, L, V] of the noisy half under this call's noise."""
        h, _, counts = self._run(
            params, state, hist_ids, rng, shard_axis, data_axis,
            {"emb_rows": emb_rows, "emb_plan": emb_plan})
        return self.logits(params, h), counts

    def per_example_loss(self, params: common.Params, state: common.State,
                         batch: Dict[str, jnp.ndarray], *, train: bool,
                         rng: Optional[jax.Array],
                         shard_axis: Optional[str] = None,
                         data_axis: Optional[str] = None, **emb
                         ) -> Tuple[jnp.ndarray, common.State]:
        """(loss a sequence [B], new state): what the trainer means over."""
        _, per_seq, counts = self._run(
            params, state, batch["hist_ids"], rng, shard_axis, data_axis,
            emb)
        return per_seq, counts
