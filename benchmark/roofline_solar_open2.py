"""Operations and bytes a train step of ``--model solar_open2`` needs,
counted from the configuration's flags and from the (position, expert) pairs
the run really routed to the experts held here; and the least time the chip
could take for them. Counted as ``roofline_kimi_linear`` counts.

Counted is the mathematics, not the implementation: of the full layer's
score matrix the causal half, the routed experts' products on the routed
pairs only (not on the buffer's spare rows), the head on the L - 1 positions
that have a next token, and nothing twice (the layers the backward pass
recomputes do not count). A matrix product costs 2 FLOPs a multiply-add
forward and twice that backward. Elementwise work (norms, convolutions,
gates, softmax, SiLU, the loss) is left out, and so is the delta-rule scan
from the step's count (it is no matrix product of the mathematics): a share
reads low, never high.

The causal scores alone (``attn_scores_least_seconds``): the score and the
value product of every (query, key) pair with key <= query, a held query
head, forward and twice that backward; against the bytes of q and o (a
query head each) and k and v (a key/value head each) in the operands' two
bytes, once each way.

The scan alone (``kda_scan_least_seconds``): the recurrence's own work a
token and head, whatever chunk length or kernel computes it
(``roofline_kimi_linear``: the decay of the state, ``k^T S``, the rank-one
update and ``S^T q``, 7 Dk Dv FLOPs forward and three times that forward and
backward; q, k, g, v, beta in and o out, float32, once each way). The write
strength's factor 2 is one more multiply a token and head, left out.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmark.roofline_kimi_linear import BYTES_PER_PARAM


def sizes(flags: dict) -> Dict[str, int]:
    return {"d": int(flags["embedding_size"]),
            "L": int(flags["history_max_len"]),
            "B": int(flags["batch_size"]),
            "layers": int(flags["decoder_layers"]),
            "every": int(flags["attn_every"]),
            "hk": int(flags["kda_heads"]), "dk": int(flags["kda_head_dim"]),
            "conv": int(flags["kda_conv"]),
            "hq": int(flags["attn_q_heads"]),
            "hkv": int(flags["attn_kv_heads"]),
            "hd": int(flags["attn_head_dim"]),
            "E": int(flags["moe_experts"]),
            "f": int(flags["moe_expert_width"]),
            "fs": int(flags["moe_shared_width"]),
            "held": int(flags["moe_experts_held"]),
            "V": int(flags["feature_size"])}


def layer_kinds(flags: dict) -> List[Tuple[str, str]]:
    """[(mixer, feed-forward)] a layer: gated GQA where ``attn_every``
    divides the layer's number (from 0), KDA elsewhere; every layer the
    expert layer."""
    s = sizes(flags)
    return [("gqa" if i % s["every"] == 0 else "kda", "moe")
            for i in range(s["layers"])]


def _least(flops: float, nbytes: float, peaks: dict) -> dict:
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here: each mixer's (with the block's two norms), an
    expert layer's, the embedding's and head's, all."""
    s = sizes(flags)
    d, hk, q, kv = (s["d"], s["hk"] * s["dk"], s["hq"] * s["hd"],
                    s["hkv"] * s["hd"])
    gqa = d * q + 2 * d * kv + d * q + q * d + 2 * d    # wq, wk wv, gate, wo
    kda = (3 * d * hk + 3 * s["conv"] * hk        # q, k, v and their taps
           + 2 * (d * s["dk"] + s["dk"] * hk)     # the two gates' bottlenecks
           + hk + s["hk"]                         # dt_bias, a_log
           + d * s["hk"]                          # beta
           + s["dk"] + hk * d                     # the output's norm, wo
           + 2 * d)                               # norm1, norm2
    moe = d * s["E"] + s["held"] * 3 * d * s["f"] + 3 * d * s["fs"]
    ends = 2 * s["V"] * d + d
    parts = {"gqa": gqa, "kda": kda, "moe": moe, "ends": ends}
    return {**parts, "all": ends + sum(parts[m] + parts[f]
                                       for m, f in layer_kinds(flags))}


def forward_flops(flags: dict, pairs: float) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part (matrix products).
    ``pairs`` is the step's (position, expert) pairs on held experts,
    summed over the layers."""
    s = sizes(flags)
    kinds = layer_kinds(flags)
    n_gqa = sum(m == "gqa" for m, _ in kinds)
    n_kda = len(kinds) - n_gqa
    positions = s["B"] * s["L"]
    d, hk, q, kv = (s["d"], s["hk"] * s["dk"], s["hq"] * s["hd"],
                    s["hkv"] * s["hd"])
    return {
        "gqa_projections": 2.0 * positions * n_gqa * (
            d * q + 2 * d * kv + d * q + q * d),
        # scores and values, each head_dim wide, on the causal half
        "gqa_attention": 2.0 * s["B"] * n_gqa * s["hq"]
        * (s["L"] * (s["L"] + 1) // 2) * 2 * s["hd"],
        "kda_projections": 2.0 * positions * n_kda * (
            3 * d * hk + 2 * (d * s["dk"] + s["dk"] * hk) + d * s["hk"]
            + hk * d),
        "router": 2.0 * positions * len(kinds) * d * s["E"],
        "experts": 2.0 * pairs * 3 * d * s["f"],
        "shared": 2.0 * positions * len(kinds) * 3 * d * s["fs"],
        "head": 2.0 * s["B"] * (s["L"] - 1) * d * s["V"],
    }


def train_step_least_seconds(flags: dict, pairs: float, peaks: dict) -> dict:
    """The least time of one step: the larger of its matrix products' FLOPs
    (forward and backward: three times the forward's) over the peak rate and
    its parameters' bytes over the peak bandwidth."""
    return _least(3.0 * sum(forward_flops(flags, pairs).values()),
                  float(BYTES_PER_PARAM * param_count(flags)["all"]), peaks)


def attn_scores_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's causal score and value products, forward
    and backward, over every full layer and held head (the module's
    docstring)."""
    s = sizes(flags)
    n_gqa = sum(m == "gqa" for m, _ in layer_kinds(flags))
    flops = 3.0 * forward_flops(flags, 0.0)["gqa_attention"]
    nbytes = 2.0 * 2 * (2 * s["hq"] + 2 * s["hkv"]) * s["hd"] \
        * s["B"] * s["L"] * n_gqa
    return _least(flops, nbytes, peaks)


def kda_scan_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's delta-rule scans, forward and backward,
    over every KDA layer and held head (the module's docstring)."""
    s = sizes(flags)
    n_kda = sum(m == "kda" for m, _ in layer_kinds(flags))
    token_heads = s["B"] * s["L"] * s["hk"] * n_kda
    return _least(3.0 * 7 * s["dk"] * s["dk"] * token_heads,
                  2.0 * 4 * (3 * s["dk"] + 2 * s["dk"] + 1) * token_heads,
                  peaks)
