"""The three older stacks that run ``sdar_moe.attention`` (SDAR, LFM2,
Solar-Open2), each a short stack of its cell's own widths compiled for a
*described* v5e (no chip attached, nothing runs; the fixtures are
``conftest.py``'s): with the leaves and steps absent that PR 53 made the
layer's choice (no output gate or a gate only, QK-norm or none, a rotation
or none), the compiled step is a pinned one, held as its instructions
counted by opcode (``step_opcodes_shared_attention.json``: counted by the
same expression on the tree that pinned it, so the test needs no parent
checkout). PR 53 held the three to PR 52's counts. PR 54 counted them anew,
on purpose: a layer by the kernel keeps the forward kernel's output and
log-sum-exp (``ops/block_attention.KEPT``), so the second
``splash_mqa_fwd_residuals`` call of each kernel layer is gone with what fed
only it. Against PR 52's counts (8,251 / 5,650 / 9,481 instructions): LFM2
8,177 (``custom-call`` 150 -> 145, ``copy-start`` / ``copy-done`` 273 ->
254, ``slice-start`` / ``slice-done`` 213 -> 199, ``bitcast`` -2,
``convert``, ``copy`` and ``get-tuple-element`` -1, ``reduce-precision``
and ``reshape`` +1); Solar-Open2 9,417 (``custom-call`` 181 -> 176,
``copy-start`` / ``copy-done`` 381 -> 366, ``slice-start`` /
``slice-done`` 424 -> 408, ``bitcast`` and ``convert`` -1, ``fusion``,
``get-tuple-element``, ``parameter``, ``reduce-precision`` and ``tuple``
+1); SDAR 5,726, more, not fewer: the call leaves the scan's backward body,
and the two kept tensors become the scan's stacked outputs
(``dynamic-update-slice`` 20 -> 22 written, ``dynamic-slice`` 37 -> 39
read, ``copy-start`` / ``copy-done`` 155 -> 168, ``fusion`` 298 -> 306,
``parameter`` 901 -> 917, ``custom-call`` 98 -> 101, ``constant`` +5,
``get-tuple-element`` +8, ``reduce-precision`` and ``bitcast`` +2,
``add``, ``reduce`` and ``slice`` +1, ``convert`` -1). The file's fourth
entry is the whole Kimi-Linear cell's step, which holds no block kernel:
counted on PR 53's tree and the same on PR 54's
(``test_tpu_compile_kimi_linear.py`` reads it)."""

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
