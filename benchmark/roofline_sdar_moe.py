"""Operations and bytes a train step of ``--model sdar_moe`` needs, counted
from the configuration's flags and from the (position, expert) pairs the run
really routed to the experts held here; and the least time the chip could
take for them.

Counted is what the algorithm needs, not what the program computes: of the
2L x 2L score matrix only the entries the block-diffusion mask allows
(``allowed_scores``: about a quarter), the experts' products on the routed
pairs only (not on the buffer's spare rows), and nothing twice (the layers
the backward pass recomputes do not count). A matrix product costs 2 FLOPs a
multiply-add forward and twice that backward (weight and input gradient);
the token table's rows have no input gradient to take. Elementwise work
(norms, softmax, rotary, SiLU, the loss) is left out, so a share reads low,
never high.
"""

from __future__ import annotations

from typing import Dict

#: Bytes the step moves for each parameter, all float32: Adam reads the
#: weight, the gradient and two moments and writes the weight and the moments
#: (28), the backward pass writes the gradient (4), forward and backward read
#: the weight once each (8). Activations are not counted.
BYTES_PER_PARAM = 40


def sizes(flags: dict) -> Dict[str, int]:
    length = int(flags["history_max_len"])
    return {"d": int(flags["embedding_size"]), "L": length,
            "B": int(flags["batch_size"]), "b": int(flags["diffusion_block"]),
            "layers": int(flags["decoder_layers"]),
            "hq": int(flags["attn_q_heads"]) * int(flags["attn_head_dim"]),
            "hkv": int(flags["attn_kv_heads"]) * int(flags["attn_head_dim"]),
            "hd": int(flags["attn_head_dim"]),
            "E": int(flags["moe_experts"]), "f": int(flags["moe_expert_width"]),
            "held": int(flags["moe_experts_held"]),
            "V": int(flags["feature_size"])}


def param_count(flags: dict) -> Dict[str, int]:
    """Parameters held here: a layer's, the embedding's and head's, all."""
    s = sizes(flags)
    layer = (s["d"] * s["hq"] + 2 * s["d"] * s["hkv"] + s["hq"] * s["d"]
             + 2 * s["d"] + 2 * s["hd"] + s["d"] * s["E"]
             + s["held"] * 3 * s["d"] * s["f"])
    ends = 2 * s["V"] * s["d"] + s["d"]
    return {"layer": layer, "ends": ends,
            "all": s["layers"] * layer + ends}


def allowed_scores(length: int, block: int) -> int:
    """Entries of one sequence's 2L x 2L score matrix the mask allows: a
    noisy query of block n reads b noisy and n*b clean keys, a clean one
    (n+1)*b clean keys: b*b*nb*(nb+1) in all, nb = L / b."""
    nb = length // block
    return block * block * nb * (nb + 1)


def forward_flops(flags: dict, pairs: float) -> Dict[str, float]:
    """FLOPs of one step's forward pass by part. ``pairs`` is the step's
    (position, expert) pairs on held experts, summed over the layers."""
    s = sizes(flags)
    positions = s["B"] * 2 * s["L"]
    return {
        "projections": 2.0 * positions * s["layers"] * (
            s["d"] * (s["hq"] + 2 * s["hkv"]) + s["hq"] * s["d"]),
        "attention": 2.0 * 2 * s["B"] * s["layers"] * s["hq"]
        * allowed_scores(s["L"], s["b"]),
        "router": 2.0 * positions * s["layers"] * s["d"] * s["E"],
        "experts": 2.0 * pairs * 3 * s["d"] * s["f"],
        "head": 2.0 * s["B"] * s["L"] * s["d"] * s["V"],
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
