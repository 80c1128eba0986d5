"""Profiling / tracing subsystem (SURVEY.md §5 "tracing" equivalent).

The reference ships with profiling *disabled* (``debugger_hook_config=False,
disable_profiler=True``, ``deepfm-sagemaker-ps-cpu.ipynb:117-118``) and tunes
via MKL/OMP env + thread pools instead. The TPU-native replacement is real
tracing: ``jax.profiler`` XPlane traces viewable in TensorBoard/Perfetto,
plus a lightweight step-time/throughput meter for always-on observability.

Usage:
    with maybe_trace(cfg.profile_dir):
        ... training steps ...

    meter = ThroughputMeter()
    meter.update(n_examples)      # per step
    meter.summary()               # {examples_per_sec, mean/p50/p99 step ms}
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Dict, Iterator, List, Optional


@contextlib.contextmanager
def maybe_trace(profile_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler trace when ``profile_dir`` is set; no-op otherwise."""
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir):
        yield


class StepWindowTracer:
    """Trace a bounded window of train steps.

    Tracing every step of a long run buffers an unloadably large XPlane
    file; the useful signal is a few steady-state steps. Starts after
    ``start_step`` (skipping compile) and stops after ``num_steps`` traced
    steps. ``on_step()`` is a fit-loop hook; ``close()`` stops an open
    trace (e.g. when the run ends inside the window). No-op when
    ``profile_dir`` is falsy.
    """

    def __init__(self, profile_dir: Optional[str], *, start_step: int = 2,
                 num_steps: int = 20):
        self.profile_dir = profile_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self._seen = 0
        self._active = False
        self._done = False

    def on_step(self, steps_done: int = 1) -> None:
        """Advance by ``steps_done`` optimizer steps (hooks fire once per
        dispatch, which covers steps_per_loop real steps)."""
        if not self.profile_dir or self._done:
            return
        import jax
        self._seen += steps_done
        if not self._active and self._seen >= self.start_step:
            jax.profiler.start_trace(self.profile_dir)
            self._active = True
        elif self._active and self._seen >= self.start_step + self.num_steps:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

    def close(self) -> None:
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
            self._done = True


#: The ``jax.named_scope`` names of the train step's phases (TUNING §17):
#: every HLO instruction's ``op_name`` carries the scopes it was traced under,
#: forward and backward (``transpose(jvp(embed))``).
STEP_SCOPES = ("embed", "fm", "cross", "bottom", "tower", "attn",
               "attn_scores", "attn_scores_window", "kda", "kda_scan", "conv",
               "conv_taps", "mamba", "mamba_scan", "gmu", "mlp", "moe", "mtp",
               "head", "mtp_head", "loss", "l2", "opt")

_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_SCOPE_COMPONENT = re.compile(r"^(?:\w+\()*(\w+)\)*$")


def innermost_scope(op_name: str, scopes=STEP_SCOPES) -> str:
    """The last of ``scopes`` on an ``op_name`` path such as
    ``jit(multi)/while/body/transpose(jvp(opt))/l2/reduce_sum`` (here
    ``l2``); the path's last component is the primitive and is not looked
    at. Empty when the path crosses none of them."""
    found = ""
    for part in op_name.split("/")[:-1]:
        m = _SCOPE_COMPONENT.match(part)
        if m and m.group(1) in scopes:
            found = m.group(1)
    return found


def hlo_op_scopes(hlo_text: str, scopes=STEP_SCOPES) -> Dict[str, str]:
    """{instruction name: innermost scope, or "" for none} over every
    instruction of a compiled program's text (``Compiled.as_text()``). A
    fusion carries its root's ``op_name``, so it belongs to its root's
    scope."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m:
            op_name = _HLO_OP_NAME.search(line)
            out[m.group(1)] = (innermost_scope(op_name.group(1), scopes)
                               if op_name else "")
    return out


def whole_instructions(hlo_text: str) -> str:
    """``hlo_text`` with every instruction on one line. A Pallas call that
    hands the profiler a note (``pallas_call(metadata=...)``, as JAX's
    attention kernels do) prints it as a ``frontend_attributes`` group with
    line breaks inside, so the instruction's own ``metadata={op_name=...}``
    lands on a line that names no instruction and the line-by-line readers
    below lose its scope; a line whose braces are still open takes the next
    ones until they close. Text without such a group comes back as it
    was."""
    out, held, depth = [], [], 0
    for line in hlo_text.splitlines():
        if not held and not _HLO_INSTRUCTION.match(line):
            out.append(line)
            continue
        held.append(line)
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            out.append(" ".join(held))
            held, depth = [], 0
    out.extend(held)
    return "\n".join(out)


def scope_kernels(hlo_text: str, kernel_scopes) -> str:
    """``hlo_text`` with the kernels the compiler names itself put under the
    scope their model declares for them: ``kernel_scopes`` is ((instruction
    name prefix, scope), ...). XLA's TPU backend compiles some primitives
    (``jax.lax.ragged_dot``) to a kernel whose ``op_name`` is the kernel's
    own name (``ragged-dot-none``) and says nothing of where it was traced;
    such an ``op_name``, one without a path, gets the scope ahead of it
    (``moe/ragged-dot-none``), and ``hlo_op_scopes`` reads it like any
    other."""
    if not kernel_scopes:
        return hlo_text
    lines = hlo_text.splitlines()
    for i, line in enumerate(lines):
        m = _HLO_INSTRUCTION.match(line)
        scope = m and next((s for prefix, s in kernel_scopes
                            if m.group(1).startswith(prefix)), None)
        op_name = scope and _HLO_OP_NAME.search(line)
        if op_name and "/" not in op_name.group(1):
            at = op_name.start(1)
            lines[i] = f"{line[:at]}{scope}/{line[at:]}"
    return "\n".join(lines)


_HLO_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_HLO_CALLS = re.compile(r"\b(calls|body)=%?([\w.\-]+)")
_HLO_ALIAS_PAIR = re.compile(r'\{"indices":\["(\d+)","(\d+)"\]\}')
#: Opcodes that move no bytes: a table-shaped result of one is a name for a
#: buffer another instruction made.
_HLO_PLUMBING = frozenset(("parameter", "get-tuple-element", "tuple", "while",
                           "bitcast", "call", "conditional"))


def _balanced(text: str, start: int) -> int:
    """Index just past the bracket that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def hlo_table_ops(hlo_text: str, rows: int) -> List[Dict[str, object]]:
    """Every instruction of a compiled program's text (``Compiled.
    as_text()``) that *makes* an array ``rows`` tall, outside fused
    computations: one pass or more over a table in HBM each. Per
    instruction: ``name``, ``opcode``, whether it sits in a ``loop_body``,
    ``results`` (``f32[rows,32]`` per table-shaped result), ``operands``
    (names), ``tables`` (those operands that name a table-shaped instruction
    or parameter), ``in_place`` (operand positions the backend aliases to an
    output: that operand's buffer is updated, not copied), the innermost
    ``scope`` of ``STEP_SCOPES`` and the ``primitive`` its ``op_name`` ends
    in (a fusion's is its root's: ``scatter`` and ``scatter-add`` cost their
    rows, not the table, when they update it in place). TUNING §5 says how
    to read the listing."""
    fused, bodies = set(), set()
    for kind, name in _HLO_CALLS.findall(hlo_text):
        (fused if kind == "calls" else bodies).add(name)
    tall = re.compile(r"\[%d[,\]]" % rows)
    out: List[Dict[str, object]] = []
    computation, tables = "", set()
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            computation, tables = m.group(2), set()
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or computation in fused:
            continue
        at = m.end()
        end = _balanced(line, at) if line[at] == "(" else line.index(" ", at)
        results = ["%s[%s]" % a for a in _HLO_ARRAY.findall(line[at:end])
                   if tall.search("[%s]" % a[1])]
        if not results:
            continue
        tables.add(m.group(1))
        paren = line.index("(", end)
        opcode = line[end:paren].strip()
        if opcode in _HLO_PLUMBING:
            continue
        operands = re.findall(r"%([\w.\-]+)",
                              line[paren:_balanced(line, paren)])
        _, _, aliasing = line.partition('"aliasing_operands"')
        op_name = _HLO_OP_NAME.search(line)
        out.append({
            "name": m.group(1), "opcode": opcode,
            "loop_body": computation in bodies, "results": results,
            "operands": operands,
            "tables": [o for o in operands if o in tables],
            "in_place": [int(i) for i, _ in
                         _HLO_ALIAS_PAIR.findall(aliasing)],
            "scope": innermost_scope(op_name.group(1)) if op_name else "",
            "primitive": (op_name.group(1).rsplit("/", 1)[-1]
                          if op_name else "")})
    return out


class ThroughputMeter:
    """Step-time and examples/sec accumulator (host wall-clock).

    Per-step wall time includes host input handoff — by design: with JAX
    async dispatch the device step overlaps the next host batch, so the
    steady-state wall time *is* the pipeline-limited step time.
    """

    def __init__(self, warmup_steps: int = 2):
        self.warmup_steps = warmup_steps
        self._step_times: List[float] = []  # per-step (interval / steps_done)
        self._total_time = 0.0
        self._examples = 0
        self._n_updates = 0
        self._n_steps = 0
        self._drain = 0.0
        self._last = time.perf_counter()

    def update(self, n_examples: int, steps_done: int = 1) -> None:
        """Record one dispatch covering ``steps_done`` optimizer steps."""
        now = time.perf_counter()
        self._n_updates += 1
        self._n_steps += steps_done
        if self._n_updates > self.warmup_steps:  # skip compile dispatches
            interval = now - self._last
            self._total_time += interval
            self._step_times.append(interval / max(steps_done, 1))
            self._examples += n_examples
        self._last = now

    def record_drain(self) -> None:
        """Fold time spent blocking on the final async-dispatched step into
        the throughput denominator (without polluting step percentiles) —
        call after jax.block_until_ready on the last step's outputs."""
        now = time.perf_counter()
        self._drain += now - self._last
        self._last = now

    def summary(self) -> Dict[str, float]:
        if not self._step_times:
            return {"steps": float(self._n_steps)}
        ts = sorted(self._step_times)
        n = len(ts)
        return {
            "steps": float(self._n_steps),
            "examples_per_sec": self._examples / max(
                self._total_time + self._drain, 1e-9),
            "step_ms_mean": 1000.0 * sum(ts) / n,
            "step_ms_p50": 1000.0 * ts[n // 2],
            # nearest-rank p99: ceil(0.99n)-1, not int(0.99n) (which would
            # report the max for any n <= 100)
            "step_ms_p99": 1000.0 * ts[max(0, -(-99 * n // 100) - 1)],
        }
