"""Operations and bytes a train step of ``--model kimi_linear`` needs,
counted from the configuration's flags and from the (position, expert) pairs
the run really routed to the experts held here; and the least time the chip
could take for them.

Counted is the mathematics, not the implementation: of MLA's score matrix
the causal half, the routed experts' products on the routed pairs only (not
on the buffer's spare rows), the head on the L - 1 positions that have a
next token, and nothing twice (the layers the backward pass recomputes do
not count). A matrix product costs 2 FLOPs a multiply-add forward and twice
that backward. Elementwise work (norms, convolutions, gates, softmax, SiLU,
the loss) is left out, and so is the delta-rule scan from the step's count
(it is no matrix product of the mathematics): a share reads low, never high.

The scan alone (``kda_scan_least_seconds``): the recurrence's own work a
token and head, whatever chunk length or kernel computes it: the decay of
the state (Dk Dv), ``k^T S`` (2 Dk Dv), the rank-one update (2 Dk Dv) and
``S^T q`` (2 Dk Dv), 7 Dk Dv FLOPs forward and three times that forward and
backward; against the bytes of q, k, g (Dk each), v (Dv) and beta in and o
(Dv) out, float32, once each way.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

#: (``roofline_sdar_moe.BYTES_PER_PARAM``: Adam's sweep 28, the gradient's
#: write 4, forward and backward a read each 8.)
BYTES_PER_PARAM = 40


def sizes(flags: dict) -> Dict[str, int]:
    return {"d": int(flags["embedding_size"]),
            "L": int(flags["history_max_len"]),
            "B": int(flags["batch_size"]),
            "layers": int(flags["decoder_layers"]),
            "every": int(flags["attn_every"]),
            "dense": int(flags["dense_layers"]),
            "hk": int(flags["kda_heads"]), "dk": int(flags["kda_head_dim"]),
            "conv": int(flags["kda_conv"]),
            "h": int(flags["attn_q_heads"]),
            "hd": int(flags["attn_head_dim"]),
            "rope": int(flags["mla_rope_dim"]),
            "latent": int(flags["mla_latent_dim"]),
            "F": int(flags["dense_mlp_width"]),
            "E": int(flags["moe_experts"]),
            "f": int(flags["moe_expert_width"]),
            "fs": int(flags["moe_shared_width"]),
            "held": int(flags["moe_experts_held"]),
            "V": int(flags["feature_size"])}


def layer_kinds(flags: dict) -> List[Tuple[str, str]]:
    """[(mixer, feed-forward)] a layer: MLA where ``attn_every`` divides the
    layer's number (from 1), KDA elsewhere; the first ``dense_layers`` a
    dense MLP, the rest the expert layer."""
    s = sizes(flags)
    return [("mla" if (i + 1) % s["every"] == 0 else "kda",
             "mlp" if i < s["dense"] else "moe") for i in range(s["layers"])]


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here: each mixer's, each feed-forward's (with the
    block's two norms in the mixer's), the embedding's and head's, all."""
    s = sizes(flags)
    d, hk = s["d"], s["hk"] * s["dk"]
    kda = (3 * d * hk + 3 * s["conv"] * hk        # q, k, v and their taps
           + 2 * (d * s["dk"] + s["dk"] * hk)     # the two gates' bottlenecks
           + hk + s["hk"]                         # dt_bias, a_log
           + d * s["hk"]                          # beta
           + s["dk"] + hk * d                     # the output's norm, wo
           + 2 * d)                               # norm1, norm2
    mla = (d * s["h"] * (s["hd"] + s["rope"]) + d * (s["latent"] + s["rope"])
           + s["latent"] + s["latent"] * s["h"] * 2 * s["hd"]
           + s["h"] * s["hd"] * d + 2 * d)
    mlp = 3 * d * s["F"]
    moe = d * s["E"] + s["held"] * 3 * d * s["f"] + 3 * d * s["fs"]
    ends = 2 * s["V"] * d + d
    kinds = layer_kinds(flags)
    parts = {"kda": kda, "mla": mla, "mlp": mlp, "moe": moe, "ends": ends}
    return {**parts, "all": ends + sum(parts[m] + parts[f]
                                       for m, f in kinds)}


def forward_flops(flags: dict, pairs: float) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part (matrix products).
    ``pairs`` is the step's (position, expert) pairs on held experts,
    summed over the layers."""
    s = sizes(flags)
    kinds = layer_kinds(flags)
    n_kda = sum(m == "kda" for m, _ in kinds)
    n_mla = len(kinds) - n_kda
    n_moe = sum(f == "moe" for _, f in kinds)
    positions = s["B"] * s["L"]
    d, hk = s["d"], s["hk"] * s["dk"]
    return {
        "kda_projections": 2.0 * positions * n_kda * (
            3 * d * hk + 2 * (d * s["dk"] + s["dk"] * hk) + d * s["hk"]
            + hk * d),
        "mla_projections": 2.0 * positions * n_mla * (
            d * s["h"] * (s["hd"] + s["rope"]) + d * (s["latent"] + s["rope"])
            + s["latent"] * s["h"] * 2 * s["hd"] + s["h"] * s["hd"] * d),
        # scores (hd + rope wide) and values (hd wide) on the causal half
        "mla_attention": 2.0 * s["B"] * n_mla * s["h"]
        * (s["L"] * (s["L"] + 1) // 2) * (2 * s["hd"] + s["rope"]),
        "router": 2.0 * positions * n_moe * d * s["E"],
        "experts": 2.0 * pairs * 3 * d * s["f"],
        "shared": 2.0 * positions * n_moe * 3 * d * s["fs"],
        "dense_mlp": 2.0 * positions * (len(kinds) - n_moe) * 3 * d * s["F"],
        "head": 2.0 * s["B"] * (s["L"] - 1) * d * s["V"],
    }


def train_step_least_seconds(flags: dict, pairs: float, peaks: dict) -> dict:
    """The least time of one step: the larger of its matrix products' FLOPs
    (forward and backward: three times the forward's) over the peak rate and
    its parameters' bytes over the peak bandwidth."""
    flops = 3.0 * sum(forward_flops(flags, pairs).values())
    nbytes = float(BYTES_PER_PARAM * param_count(flags)["all"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}


def kda_scan_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's delta-rule scans, forward and backward,
    over every KDA layer and held head (the module's docstring)."""
    s = sizes(flags)
    n_kda = sum(m == "kda" for m, _ in layer_kinds(flags))
    token_heads = s["B"] * s["L"] * s["hk"] * n_kda
    flops = 3.0 * 7 * s["dk"] * s["dk"] * token_heads
    nbytes = 2.0 * 4 * (3 * s["dk"] + 2 * s["dk"] + 1) * token_heads
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "seconds": max(by_flops, by_bytes),
            "bound": "flops" if by_flops >= by_bytes else "bytes"}
