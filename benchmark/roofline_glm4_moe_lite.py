"""Operations and bytes a train step of ``--model glm4_moe_lite`` needs,
counted from the configuration's flags and from the (position, expert) pairs
the run really routed to the experts held here; and the least time the chip
could take for them. Counted as ``roofline_kimi_linear`` counts.

The whole step (``train_step_least_seconds``) counts the mathematics, not
the implementation: of each block's score matrix the causal half at the
heads' own widths (keys 192 + 64, values 256), the routed experts' products
on the routed pairs only (not on the buffer's spare rows), the main head on
the L - 1 positions that have a next token and the module's on the L - 2
that have one after, the module's block and ``W_eh`` on the L - 1 positions
the module has, and nothing twice (the blocks the backward pass recomputes do
not count). A matrix product costs 2 FLOPs a multiply-add forward and twice
that backward. Elementwise work (norms, rotary, softmax, SiLU, the losses)
is left out: a share reads low, never high.

The causal scores alone (``attn_scores_least_seconds``): the score and the
value product of every (query, key) pair with key <= query, a held head and
block (the stack's layers and the module's block), forward and twice that
backward, the same whatever implements it; against the bytes of q and k
(256 wide) and v and o (256 wide) in the operands' two bytes, once each way.
"""

from __future__ import annotations

from typing import Dict

from benchmark.roofline_kimi_linear import BYTES_PER_PARAM
from benchmark.roofline_lfm2_moe import _least


def sizes(flags: dict) -> Dict[str, int]:
    return {"d": int(flags["embedding_size"]),
            "L": int(flags["history_max_len"]),
            "B": int(flags["batch_size"]),
            "layers": int(flags["decoder_layers"]),
            "dense": int(flags["dense_layers"]),
            "mtp": int(flags.get("mtp_depth", 0)),
            "h": int(flags["attn_q_heads"]),
            "rank": int(flags["mla_q_rank"]),
            "latent": int(flags["mla_latent_dim"]),
            "kd": int(flags["mla_nope_dim"]) + int(flags["mla_rope_dim"]),
            "nope": int(flags["mla_nope_dim"]),
            "rope": int(flags["mla_rope_dim"]),
            "vd": int(flags["mla_value_dim"]),
            "F": int(flags["dense_mlp_width"]),
            "E": int(flags["moe_experts"]),
            "f": int(flags["moe_expert_width"]),
            "fs": int(flags["moe_shared_width"]),
            "held": int(flags["moe_experts_held"]),
            "V": int(flags["feature_size"])}


def _mla_products(s: Dict[str, int]) -> int:
    """Multiply-adds a position of one MLA's five products."""
    return (s["d"] * s["rank"] + s["rank"] * s["h"] * s["kd"]
            + s["d"] * (s["latent"] + s["rope"])
            + s["latent"] * s["h"] * (s["nope"] + s["vd"])
            + s["h"] * s["vd"] * s["d"])


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here: an MLA's (with the block's two norms), each
    feed-forward's, the module's own, the embedding's and head's, all."""
    s = sizes(flags)
    d = s["d"]
    parts = {"mla": _mla_products(s) + s["rank"] + s["latent"] + 2 * d,
             "mlp": 3 * d * s["F"],
             "moe": d * s["E"] + s["held"] * 3 * d * s["f"] + 3 * d * s["fs"],
             "mtp_own": 2 * d * d + 3 * d,
             "ends": 2 * s["V"] * d + d}
    sparse = s["layers"] - s["dense"] + s["mtp"]
    return {**parts, "all": parts["ends"] + s["mtp"] * parts["mtp_own"]
            + (s["layers"] + s["mtp"]) * parts["mla"]
            + s["dense"] * parts["mlp"] + sparse * parts["moe"]}


def forward_flops(flags: dict, pairs: float) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part (matrix products).
    ``pairs`` is the step's (position, expert) pairs on held experts,
    summed over the expert blocks."""
    s = sizes(flags)
    b, length, d = s["B"], s["L"], s["d"]
    # positions the stack's layers run, and the module's block
    stack, module = b * length * s["layers"], b * (length - 1) * s["mtp"]
    sparse = b * length * (s["layers"] - s["dense"]) + module
    causal = (s["layers"] * (length * (length + 1) // 2)
              + s["mtp"] * ((length - 1) * length // 2))
    return {
        "mla_projections": 2.0 * (stack + module) * _mla_products(s),
        # scores (kd wide) and values (vd wide) on the causal half
        "attn_scores": 2.0 * b * s["h"] * causal * (s["kd"] + s["vd"]),
        "router": 2.0 * sparse * d * s["E"],
        "experts": 2.0 * pairs * 3 * d * s["f"],
        "shared": 2.0 * sparse * 3 * d * s["fs"],
        "dense_mlp": 2.0 * b * length * s["dense"] * 3 * d * s["F"],
        "mtp_w_eh": 2.0 * module * 2 * d * d,
        "head": 2.0 * b * (length - 1) * d * s["V"],
        "mtp_head": 2.0 * s["mtp"] * b * (length - 2) * d * s["V"],
    }


def train_step_least_seconds(flags: dict, pairs: float, peaks: dict) -> dict:
    """The least time of one step: the larger of its matrix products' FLOPs
    (forward and backward: three times the forward's) over the peak rate and
    its parameters' bytes over the peak bandwidth."""
    return _least(3.0 * sum(forward_flops(flags, pairs).values()),
                  float(BYTES_PER_PARAM * param_count(flags)["all"]), peaks)


def attn_scores_least_seconds(flags: dict, peaks: dict) -> dict:
    """The least time of one step's causal score and value products,
    forward and backward, over every block and held head (the module's
    docstring)."""
    s = sizes(flags)
    flops = 3.0 * forward_flops(flags, 0.0)["attn_scores"]
    nbytes = 2.0 * 2 * s["h"] * 2 * (s["kd"] + s["vd"]) \
        * s["B"] * s["L"] * (s["layers"] + s["mtp"])
    return _least(flops, nbytes, peaks)
