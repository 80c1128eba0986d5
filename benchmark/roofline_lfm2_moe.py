"""Operations and bytes a train step of ``--model lfm2_moe`` needs, counted
from the configuration's flags and from the (position, expert) pairs the run
really routed to the experts held here; and the least time the chip could
take for them. Counted as ``roofline_kimi_linear`` counts.

The whole step (``train_step_least_seconds``) counts the mathematics, not
the implementation: of the full layer's score matrix the causal half at the
head's real 64 lanes (a padded lane is no work), the routed experts'
products on the routed pairs only (not on the buffer's spare rows), the head
on the L - 1 positions that have a next token, and nothing twice (the layers
the backward pass recomputes do not count). A matrix product costs 2 FLOPs a
multiply-add forward and twice that backward. Elementwise work (norms, the
convolution's taps and gates, rotary, softmax, SiLU, the loss) is left out:
a share reads low, never high. The table is counted once: it is the head.

The causal scores alone (``attn_scores_least_seconds``): the score and the
value product of every (query, key) pair with key <= query over the head's
64 real lanes, a held query head, forward and twice that backward; against
the bytes of q and o (a query head each) and k and v (a key/value head each)
in the operands' two bytes, once each way.

The convolution mixer alone (``conv_least_seconds``), **as the step runs
it**: its two products (``[T, d] x [d, 3d]`` and ``[T, d] x [d, d]``) four
times over (forward, the layer's recomputation, and the backward pass's two
products each) at the bf16 peak, plus its elementwise passes' bytes at the
peak bandwidth: forward and recomputed, B, C and u read in float32 and the
second product's operand written in two bytes (14 d bytes a position each
time); backward, that operand's cotangent read in float32, B, C and u read
again and their cotangents written in the first product's two bytes (22 d).
The products and the passes are added, not overlapped: the taps shift
positions, which no product's epilogue does. The norm and the casts of the
weights are in the scope's time and in no count.
"""

from __future__ import annotations

import operator
from typing import Dict, List, Tuple

from benchmark.roofline_kimi_linear import BYTES_PER_PARAM


def sizes(flags: dict) -> Dict[str, int]:
    return {"d": int(flags["embedding_size"]),
            "L": int(flags["history_max_len"]),
            "B": int(flags["batch_size"]),
            "taps": int(flags.get("conv_taps", 3)),
            "hq": int(flags["attn_q_heads"]),
            "hkv": int(flags["attn_kv_heads"]),
            "hd": int(flags["attn_head_dim"]),
            "F": int(flags["dense_mlp_width"]),
            "E": int(flags["moe_experts"]),
            "f": int(flags["moe_expert_width"]),
            "held": int(flags["moe_experts_held"]),
            "V": int(flags["feature_size"])}


def layer_kinds(flags: dict) -> List[Tuple[str, str]]:
    """[(mixer, feed-forward)] a layer: the mixers are ``layer_types``'
    words; the first ``dense_layers`` a dense MLP, the rest the expert
    layer."""
    dense = int(flags["dense_layers"])
    mixers = [w.strip() for w in str(flags["layer_types"]).split(",")
              if w.strip()]
    return [(m, "mlp" if i < dense else "moe") for i, m in enumerate(mixers)]


def _least(flops: float, nbytes: float, peaks: dict, together=max) -> dict:
    """The least time of ``flops`` and ``nbytes``: the larger of the two
    times where they may overlap, ``together=operator.add`` where they may
    not."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": together(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here: each mixer's (with the block's two norms), each
    feed-forward's, the tied table's with the final norm, all."""
    s = sizes(flags)
    d, q, kv = s["d"], s["hq"] * s["hd"], s["hkv"] * s["hd"]
    parts = {"conv": 3 * d * d + s["taps"] * d + d * d + 2 * d,
             "full_attention": 2 * d * q + 2 * d * kv + 2 * s["hd"] + 2 * d,
             "mlp": 3 * d * s["F"],
             "moe": d * s["E"] + s["held"] * 3 * d * s["f"],
             "ends": s["V"] * d + d}
    return {**parts, "all": parts["ends"] + sum(
        parts[m] + parts[f] for m, f in layer_kinds(flags))}


def forward_flops(flags: dict, pairs: float) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part (matrix products).
    ``pairs`` is the step's (position, expert) pairs on held experts,
    summed over the layers."""
    s = sizes(flags)
    kinds = layer_kinds(flags)
    n_conv = sum(m == "conv" for m, _ in kinds)
    n_attn = len(kinds) - n_conv
    n_moe = sum(f == "moe" for _, f in kinds)
    positions = s["B"] * s["L"]
    d, q, kv = s["d"], s["hq"] * s["hd"], s["hkv"] * s["hd"]
    return {
        "conv_products": 2.0 * positions * n_conv * 4 * d * d,
        "attn_projections": 2.0 * positions * n_attn * (
            2 * d * q + 2 * d * kv),
        # scores and values, each head_dim wide, on the causal half
        "attn_scores": 2.0 * s["B"] * n_attn * s["hq"]
        * (s["L"] * (s["L"] + 1) // 2) * 2 * s["hd"],
        "dense_mlp": 2.0 * positions * (len(kinds) - n_moe) * 3 * d * s["F"],
        "router": 2.0 * positions * n_moe * d * s["E"],
        "experts": 2.0 * pairs * 3 * d * s["f"],
        "head": 2.0 * s["B"] * (s["L"] - 1) * d * s["V"],
    }


def train_step_least_seconds(flags: dict, pairs: float, peaks: dict) -> dict:
    """The least time of one step: the larger of its matrix products' FLOPs
    (forward and backward: three times the forward's) over the peak rate and
    its parameters' bytes over the peak bandwidth."""
    return _least(3.0 * sum(forward_flops(flags, pairs).values()),
                  float(BYTES_PER_PARAM * param_count(flags)["all"]), peaks)


def attn_scores_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's causal score and value products at the
    head's real lanes, forward and backward, over every full layer and held
    head (the module's docstring)."""
    s = sizes(flags)
    n_attn = sum(m == "full_attention" for m, _ in layer_kinds(flags))
    flops = 3.0 * forward_flops(flags, 0.0)["attn_scores"]
    nbytes = 2.0 * 2 * (2 * s["hq"] + 2 * s["hkv"]) * s["hd"] \
        * s["B"] * s["L"] * n_attn
    return _least(flops, nbytes, peaks)


def conv_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's convolution mixers as the step runs
    them: the two products four times over at the bf16 peak plus the
    elementwise passes' bytes at the peak bandwidth (the module's
    docstring)."""
    s = sizes(flags)
    n_conv = sum(m == "conv" for m, _ in layer_kinds(flags))
    positions = s["B"] * s["L"]
    return _least(4.0 * forward_flops(flags, 0.0)["conv_products"],
                  float((2 * 14 + 22) * s["d"] * positions * n_conv), peaks,
                  together=operator.add)
