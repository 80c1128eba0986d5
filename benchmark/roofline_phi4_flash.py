"""Operations and bytes a train step of ``--model phi4_flash`` needs, counted
from the configuration's flags, and the least time the chip could take for
them. Counted as ``roofline_kimi_linear`` counts: the mathematics, not the
implementation, and nothing twice (the layers the backward pass recomputes
do not count). A matrix product costs 2 FLOPs a multiply-add forward and
twice that backward. Elementwise work (norms, the convolution's taps, SiLU,
softplus, softmax, the gates, the loss) is left out of the products' counts:
a share reads low, never high. The table is counted once: it is the head.

The whole step (``train_step_least_seconds``): every matrix product (the
mixers' projections, the MLPs, the head on the L - 1 positions that have a
next token) and the attention layers' score and value products on the pairs
their masks allow, forward and twice that backward, over the bf16 peak;
against the parameters' bytes (``BYTES_PER_PARAM``) over the peak bandwidth;
the larger. The scan is no matrix product and is left out of it.

The dense products alone (``matmul_flops``): the step's count less the
attention's scores and values.

The selective scan alone (``mamba_scan_least_seconds``): the recurrence's
own work a position, channel and state, the same whatever implements it (a
loop over positions, chunks, a kernel): the step's log-decay (1), its
exponential (1), the state's decay (1), the input's product and sum (2), the
output's product and sum (2): 7 operations forward and three times that
forward and backward, over the bf16 peak (the chip's one published rate; the
vector unit's is lower, so the bound below is never this one); against the
bytes of x and the step size in and y out ([T, C] each) and B and C
([T, N] each), float32, once each way; the larger.

The masked scores alone (``attn_scores_least_seconds``): of every query pair
of heads two softmax maps, each a score product over the head's 64 lanes and
a value product over the pair's 128, on the (query, key) pairs the layer's
mask allows: ``min(t + 1, window)`` keys a query under the window, ``t + 1``
under the causal mask (the full and the cross layers); forward and twice
that backward; against the bytes of q (a query head each), of both maps'
128-wide results, and of the keys and the pair's values under each key
head, in the operands' two bytes, once each way; the larger.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.roofline_kimi_linear import BYTES_PER_PARAM
from benchmark.roofline_lfm2_moe import _least

ATTENDS = ("window_attention", "full_attention", "cross_attention")


def sizes(flags: dict) -> Dict[str, int]:
    d = int(flags["embedding_size"])
    return {"d": d, "L": int(flags["history_max_len"]),
            "B": int(flags["batch_size"]),
            "C": int(flags["mamba_expand"]) * d,
            "N": int(flags["mamba_state"]), "taps": int(flags["mamba_conv"]),
            "rank": int(flags["mamba_dt_rank"]),
            "hq": int(flags["attn_q_heads"]),
            "hkv": int(flags["attn_kv_heads"]),
            "hd": int(flags["attn_head_dim"]),
            "window": int(flags["attn_window"]),
            "F": int(flags["dense_mlp_width"]),
            "V": int(flags["feature_size"])}


def mixers(flags: dict) -> List[str]:
    return [w.strip() for w in str(flags["layer_types"]).split(",")
            if w.strip()]


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here by kind of layer (the mixer, the MLP and the
    block's two LayerNorms), the tied table's with the final norm, all."""
    s = sizes(flags)
    d, c, n, r = s["d"], s["C"], s["N"], s["rank"]
    q, kv, hd = s["hq"] * s["hd"], s["hkv"] * s["hd"], s["hd"]
    block = 3 * d * s["F"] + 4 * d
    own = 2 * (d * q + q) + 4 * hd + 2 * hd     # q, o, lambdas, the sub-norm
    parts = {
        "mamba": block + 2 * d * c + s["taps"] * c + c + c * (r + 2 * n)
        + r * c + c + c * n + c + c * d,
        "gmu": block + 2 * d * c,
        "window_attention": block + own + 2 * (d * kv + kv),
        "full_attention": block + own + 2 * (d * kv + kv),
        "cross_attention": block + own,
        "ends": s["V"] * d + 2 * d}
    return {**parts,
            "all": parts["ends"] + sum(parts[m] for m in mixers(flags))}


def allowed_pairs(flags: dict) -> Dict[str, int]:
    """(query, key) pairs a sequence's mask allows, by kind of layer."""
    s = sizes(flags)
    length, w = s["L"], min(s["window"], s["L"])
    causal = length * (length + 1) // 2
    return {"window_attention": w * (w + 1) // 2 + (length - w) * w,
            "full_attention": causal, "cross_attention": causal}


def forward_flops(flags: dict) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part (matrix products)."""
    s = sizes(flags)
    kinds = mixers(flags)
    positions = s["B"] * s["L"]
    d, c, n, r = s["d"], s["C"], s["N"], s["rank"]
    q, kv = s["hq"] * s["hd"], s["hkv"] * s["hd"]
    count = {m: kinds.count(m) for m in set(kinds)}
    own = sum(count.get(m, 0) for m in ("window_attention", "full_attention"))
    attends = own + count.get("cross_attention", 0)
    pairs = allowed_pairs(flags)
    return {
        "mamba_projections": 2.0 * positions * count.get("mamba", 0) * (
            2 * d * c + c * (r + 2 * n) + r * c + c * d),
        "gmu": 2.0 * positions * count.get("gmu", 0) * 2 * d * c,
        "attn_projections": 2.0 * positions * (attends * 2 * d * q
                                               + own * 2 * d * kv),
        # two maps a query pair: scores over hd, values over the pair's 2 hd
        "attn_scores": 2.0 * s["B"] * s["hq"] * 3 * s["hd"] * sum(
            pairs[m] * count.get(m, 0) for m in ATTENDS),
        "mlp": 2.0 * positions * len(kinds) * 3 * d * s["F"],
        "head": 2.0 * s["B"] * (s["L"] - 1) * d * s["V"],
    }


def train_step_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step: the larger of its matrix products' FLOPs
    (forward and backward: three times the forward's) over the peak rate and
    its parameters' bytes over the peak bandwidth."""
    return _least(3.0 * sum(forward_flops(flags).values()),
                  float(BYTES_PER_PARAM * param_count(flags)["all"]), peaks)


def matmul_flops(flags: dict) -> float:
    """The dense products' FLOPs of one step, forward and backward."""
    return 3.0 * sum(v for k, v in forward_flops(flags).items()
                     if k != "attn_scores")


def mamba_scan_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's selective recurrences, forward and
    backward (the module's docstring)."""
    s = sizes(flags)
    layers = mixers(flags).count("mamba")
    positions = s["B"] * s["L"]
    ops = 3.0 * 7 * positions * s["C"] * s["N"] * layers
    nbytes = 2.0 * 4 * positions * (3 * s["C"] + 2 * s["N"]) * layers
    return _least(ops, nbytes, peaks)


def attn_scores_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's masked score and value products, both
    maps of every query pair, forward and backward (the module's
    docstring)."""
    s = sizes(flags)
    layers = sum(m in ATTENDS for m in mixers(flags))
    flops = 3.0 * forward_flops(flags)["attn_scores"]
    # q and both maps' results a query head, k and the pair's values a key
    nbytes = 2.0 * 2 * s["B"] * s["L"] * layers * (
        s["hq"] * (s["hd"] + 2 * s["hd"]) + s["hkv"] * (s["hd"] + 2 * s["hd"]))
    return _least(flops, nbytes, peaks)
