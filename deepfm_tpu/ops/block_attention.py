"""Block-masked flash attention on a TPU: masked scores, softmax and values
as one Pallas call that visits only the blocks of the score matrix in which
the mask allows anything.

The kernel is the one JAX ships (``jax.experimental.pallas.ops.tpu
.splash_attention``): a grid over (query head, query block, the row's
non-empty key blocks), the scores of a block made, masked, exponentiated and
multiplied into the values without leaving VMEM, float32 running max, sum and
accumulator, and a ``custom_vjp`` whose backward kernels make the scores
again from the saved log-sum-exp. Several query heads on one key/value head
are its MQA form's head axis, so no key or value is repeated. It applies no
scale: the caller folds ``1/sqrt(head_dim)`` into q.

What the forward kernel hands the backward kernels beside its operands, its
output and the log-sum-exp, carries the name ``KEPT``: a ``jax.checkpoint``
whose policy saves the name holds those two (``kept_bytes``) and does not run
the forward kernel again in the backward pass; one that does not runs it
twice, as before the name.

What this module adds is the mask's form. The caller states its mask once, as
an elementwise function of (query index, key index) — the same function its
XLA path uses — and ``PairMask`` evaluates it block by block on the host
(NumPy; nothing [S, S] is ever held) for the pass that finds the non-empty
blocks and stores the distinct partly-allowed ones (three of 512 x 512 for
the block-diffusion mask at S = 8,192), which the kernel loads where a block
is neither empty nor full. That pass takes half a second at S = 8,192:
build a kernel once a shape and keep it. (The kernel could evaluate the
function itself on every visited block, storing nothing; on a v5e the
mask's integer arithmetic then costs more than the softmax: 1.47 ms against
0.92 ms a forward call, 5.1 against 3.3 ms forward and backward, PERF.md
section 6, PR 32.)

``supported`` says where the compiled kernel applies; ``interpret=True``
runs the same kernels through the Pallas interpreter, which is how the CPU
tests hold them to the XLA path.
"""

from __future__ import annotations

from typing import Callable, Hashable, Tuple

import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _kernel,
    splash_attention_mask as _mask,
)

#: Lanes of one vector register line: a head's rows are whole lines or half
#: lines.
LANES = 128
#: ``checkpoint_name`` of the forward kernel's output and log-sum-exp, which
#: the backward kernels read.
KEPT = "block_attention_kept"


def supported(backend: str, seq: int, head_dim: int, block: int) -> bool:
    """True where the compiled kernel applies: a TPU backend, a head whose
    rows are whole 128-lane lines or half lines, and a sequence the kernel's
    block divides. A head of 64 goes to the kernel as it is: on a v5e it
    takes the time of a head of 128 (q [2, 8192, 8, 4, 64] under the causal
    mask, forward / forward and backward 12.1 / 46.7 ms; real heads of 128
    12.4 / 46.0 ms: the MXU's line is the unit; PERF.md section 6, PR 40)."""
    return backend == "tpu" and head_dim % (LANES // 2) == 0 \
        and seq % block == 0


class PairMask(_mask.Mask):
    """A [seq, seq] mask stated as ``pairs(q_index, k_index) -> bool`` on
    integer arrays that broadcast against each other, evaluated a slice at a
    time. ``key`` says which mask it is: two masks of one key and shape are
    the same mask (the kernel's tables are cached by it)."""

    def __init__(self, seq: int, pairs: Callable, key: Hashable):
        self.seq, self.pairs, self.key = seq, pairs, key

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.seq, self.seq)

    def __getitem__(self, idx) -> np.ndarray:
        rows, cols = (np.arange(self.seq, dtype=np.int32)[i] for i in idx)
        return np.asarray(self.pairs(rows[:, None], cols[None, :]))

    def __eq__(self, other: object):
        return (isinstance(other, PairMask) and self.seq == other.seq
                and self.key == other.key)

    def __hash__(self):
        return hash((PairMask, self.seq, self.key))


def make_kernel(pairs: Callable, key: Hashable, *, seq: int, heads: int,
                block: int, interpret: bool = False):
    """The kernel for ``heads`` query heads on one key/value head over
    ``seq`` positions under the mask ``pairs``: ``kernel(q [heads, seq, D],
    k [seq, D], v [seq, D]) -> [heads, seq, D]`` in q's type. Every kernel,
    forward and backward, works in blocks of ``block`` queries by ``block``
    keys."""
    mask = _mask.MultiHeadMask([PairMask(seq, pairs, key)] * heads)
    sizes = _kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    return _kernel.make_splash_mqa_single_device(
        mask, block_sizes=sizes, residual_checkpoint_name=KEPT,
        interpret=interpret)


def kept_bytes(queries: int, value_dim: int, itemsize: int) -> int:
    """Bytes of what carries ``KEPT`` over ``queries`` (sequences x positions
    x query heads): an output of ``value_dim`` numbers of ``itemsize`` bytes
    (the operands' type) and a float32 log-sum-exp a query."""
    return queries * (value_dim * itemsize + 4)


def visited_blocks(kernel, seq: int, block: int) -> Tuple[int, int]:
    """(blocks of the score matrix the forward grid computes, all of them),
    a head: read from the kernel's own block table."""
    table = np.asarray(kernel.fwd_mask_info.block_mask)[0]
    return int(np.count_nonzero(table)), (seq // block) ** 2
