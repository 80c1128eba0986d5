"""Operations and bytes a train step of ``--model afmoe`` needs, counted from
the configuration's flags and from the (position, expert) pairs the run
really routed to the experts held here; and the least time the chip could
take for them. Counted as ``roofline_kimi_linear`` counts.

The whole step (``train_step_least_seconds``) counts the mathematics, not
the implementation: of each layer's score matrix the pairs its mask allows
(the causal half of a full layer, the band of a windowed one), the routed
experts' products on the routed pairs only (not on the buffer's spare rows),
the head on the L - 1 positions that have a next token, and nothing twice
(the layers the backward pass recomputes do not count). A matrix product
costs 2 FLOPs a multiply-add forward and twice that backward. Elementwise
work (norms, rotary, the gate, softmax, SiLU, the loss) is left out: a share
reads low, never high.

The masked scores alone (``attn_scores_least_seconds``), a mask at a time:
the score and the value product of every allowed (query, key) pair over the
head's 128 lanes, a query head and layer of that mask, forward and twice
that backward, the same whatever implements it (the band's partly masked
edge blocks and the recomputation are in the time and in no count); against
the bytes of q and o (every query head) and k and v (every key/value head)
in the operands' two bytes, once each way.
"""

from __future__ import annotations

from typing import Dict

from benchmark.roofline_kimi_linear import BYTES_PER_PARAM
from benchmark.roofline_lfm2_moe import _least

#: share of the scores -> ``sizes``' count of the layers under its mask
MASKS = {"attn_scores": "full", "attn_scores_window": "windowed"}


def sizes(flags: dict) -> Dict[str, int]:
    kinds = [k for k in str(flags["layer_types"]).split(",") if k]
    return {"d": int(flags["embedding_size"]),
            "L": int(flags["history_max_len"]),
            "B": int(flags["batch_size"]),
            "layers": int(flags["decoder_layers"]),
            "full": kinds.count("full_attention"),
            "windowed": kinds.count("window_attention"),
            "window": int(flags.get("attn_window", 0)),
            "dense": int(flags["dense_layers"]),
            "hq": int(flags["attn_q_heads"]),
            "hkv": int(flags["attn_kv_heads"]),
            "hd": int(flags["attn_head_dim"]),
            "F": int(flags["dense_mlp_width"]),
            "E": int(flags["moe_experts"]),
            "f": int(flags["moe_expert_width"]),
            "fs": int(flags["moe_shared_width"]),
            "held": int(flags["moe_experts_held"]),
            "V": int(flags["feature_size"])}


def allowed_pairs(length: int, window: int = 0) -> int:
    """(query, key) pairs a head's mask allows over ``length`` positions:
    the causal half, or under a window each query's own position and the
    ``window - 1`` before it."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _attn_products(s: Dict[str, int]) -> int:
    """Multiply-adds a position of one layer's five projections (q, the
    gate and the output over every query head, k and v over every key/value
    head)."""
    return s["d"] * s["hd"] * (3 * s["hq"] + 2 * s["hkv"])


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here: a layer's attention (with its head norms and
    the layer's four norms), each feed-forward's, the embedding's and
    head's with the final norm, all."""
    s = sizes(flags)
    d = s["d"]
    parts = {"attn": _attn_products(s) + 2 * s["hd"] + 4 * d,
             "mlp": 3 * d * s["F"],
             "moe": d * s["E"] + s["held"] * 3 * d * s["f"] + 3 * d * s["fs"],
             "ends": 2 * s["V"] * d + d}
    return {**parts, "all": parts["ends"] + s["layers"] * parts["attn"]
            + s["dense"] * parts["mlp"]
            + (s["layers"] - s["dense"]) * parts["moe"]}


def forward_flops(flags: dict, pairs: float) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part (matrix products).
    ``pairs`` is the step's (position, expert) pairs on held experts,
    summed over the expert layers."""
    s = sizes(flags)
    b, length, d = s["B"], s["L"], s["d"]
    positions = b * length
    sparse = positions * (s["layers"] - s["dense"])
    # scores and values, each ``hd`` wide, on the pairs a mask allows
    a_head = 2.0 * b * s["hq"] * 2 * s["hd"]
    return {
        "attn_projections": 2.0 * positions * s["layers"] * _attn_products(s),
        "attn_scores": a_head * s["full"] * allowed_pairs(length),
        "attn_scores_window":
            a_head * s["windowed"] * allowed_pairs(length, s["window"]),
        "router": 2.0 * sparse * d * s["E"],
        "experts": 2.0 * pairs * 3 * d * s["f"],
        "shared": 2.0 * sparse * 3 * d * s["fs"],
        "dense_mlp": 2.0 * positions * s["dense"] * 3 * d * s["F"],
        "head": 2.0 * b * (length - 1) * d * s["V"],
    }


def train_step_least_seconds(flags: dict, pairs: float, peaks: dict) -> dict:
    """The least time of one step: the larger of its matrix products' FLOPs
    (forward and backward: three times the forward's) over the peak rate and
    its parameters' bytes over the peak bandwidth."""
    return _least(3.0 * sum(forward_flops(flags, pairs).values()),
                  float(BYTES_PER_PARAM * param_count(flags)["all"]), peaks)


def attn_scores_least_seconds(flags: dict, peaks: dict,
                              share: str = "attn_scores") -> dict:
    """The least time of one step's score and value products under one mask
    (``share``: ``attn_scores`` the full layers', ``attn_scores_window`` the
    windowed ones'), forward and backward (the module's docstring)."""
    s = sizes(flags)
    flops = 3.0 * forward_flops(flags, 0.0)[share]
    nbytes = 2.0 * 2 * 2 * (s["hq"] + s["hkv"]) * s["hd"] \
        * s["B"] * s["L"] * s[MASKS[share]]
    return _least(flops, nbytes, peaks)
