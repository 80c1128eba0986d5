"""The three older stacks that run ``sdar_moe.attention`` (SDAR, LFM2,
Solar-Open2), each a short stack of its cell's own widths compiled for a
*described* v5e (no chip attached, nothing runs; the fixtures are
``conftest.py``'s): with the leaves and steps absent that PR 53 made the
layer's choice (no output gate or a gate only, QK-norm or none, a rotation
or none), the compiled step is the one PR 52's tree compiled, held as its
instructions counted by opcode (``step_opcodes_shared_attention.json``:
counted on PR 52's tree by the same expression, so the test needs no parent
checkout)."""

import collections
import json
import os
import re

import pytest

from benchmark import harness

#: an instruction's opcode: ``%name = <type> opcode(operands...``
OPCODE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (?:\([^=]*?\)|\S+) ([\w\-]+)\(")
#: configuration -> what is cut of its cell's flags: 1,024 positions, the
#: shortest stack with each of its kinds of layer, 2,048 rows
SHORT = {
    "sdar-30b-a3b": dict(decoder_layers=2),
    "lfm2-8b-a1b": dict(decoder_layers=3,
                        layer_types="conv,full_attention,conv"),
    "solar-open2-250b": dict(decoder_layers=2, attn_every=2),
}
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "step_opcodes_shared_attention.json")) as _f:
    PARENT = json.load(_f)


def opcode_counts(text: str) -> dict:
    found = collections.Counter()
    for line in text.splitlines():
        m = OPCODE.match(line)
        if m:
            found[m.group(1)] += 1
    return dict(found)


@pytest.mark.parametrize("config", sorted(SHORT))
def test_the_step_is_the_parents_instruction_for_instruction(step_for_v5e,
                                                             config):
    flags = {**harness.load_json("configs", f"{config}.json")["flags"],
             "history_max_len": 1024, "feature_size": 2048,
             "moe_pair_capacity": 4096, **SHORT[config]}
    tr, _, text = step_for_v5e(flags)
    assert tr.model.step_notes["attn_scores"] == "kernel"
    got, want = opcode_counts(text), PARENT[config]
    assert sum(got.values()) == want["instructions"]
    assert got == want["opcodes"]
