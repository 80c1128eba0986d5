"""Near-zero-overhead span tracing, exported as Chrome ``trace_event`` JSON.

One process-global :class:`Tracer` records span events into a preallocated
ring (``--trace ring``; wraparound overwrites the oldest events and COUNTS
them — never a silent loss) or an unbounded list (``--trace full``). Off
(the default) every instrumentation site costs one attribute load and a
falsy check: ``span()`` returns a shared no-op singleton, ``begin()``
returns ``None``, and no event object is ever built.

Three event shapes, all Perfetto/chrome://tracing loadable:

- ``span("name", **attrs)`` — a ``with``-block producing one complete
  ("X") event on the calling thread; nesting reconstructs from ts/dur
  containment per (pid, tid).
- ``begin("name", **attrs)`` / ``end(handle, **attrs)`` — an async
  ("b"/"e") pair sharing an id, for spans that start on one thread and
  finish on another (ring waits, executor handoffs).
- ``instant("name", **attrs)`` — a point ("i") event (spills, swaps).

While tracing is on, a ``gc.callbacks`` hook records each collection of the
interpreter's cyclic collector as a ``host.gc`` span on the thread it
stopped (``generation``, ``collected``): a stop-the-world pause blocks the
dispatch thread whichever thread triggered it. A collection can start on a
thread that is inside the tracer, holding its lock, so the hook takes no
lock: its events go to a queue of their own (``Tracer._emit_gc``).

While tracing is on, and only then, one daemon thread, the *pulse*
(``PULSE_THREAD``), sleeps to a deadline every ``PULSE_MS`` and stamps when
it woke. A beat more than ``STALL_MS`` late is a ``host.stall`` span [the
beat's due time, the time it woke]: the process ran no Python for that
long, whichever thread a reader cares about. The span carries what the
operating system says happened meanwhile (``late_ms``, ``runq_ms``,
``cpu_ms``, ``busiest_thread`` / ``busiest_cpu_ms``, ``majflt``,
``nivcsw``, ``steal_ms``, ``psi_cpu_ms`` / ``psi_mem_ms`` / ``psi_io_ms``,
``throttled_ms``; a source the machine has not leaves its key out) and a
``cause`` (:func:`stall_cause`). Once a second a ``host.pulse`` span [the
previous pulse, now] carries the second's ``beats``, ``late_ms_max`` and
the same counters' deltas: what a quiet second of this host reads.

The clock is ``time.time_ns()`` (wall), NOT ``perf_counter_ns``: traces
from several processes (trainer, input workers, drill) merge into ONE
timeline, so timestamps must share an epoch.

Correlation ids: :func:`new_trace_id` mints process-unique int ids
(``pid << 20 | counter``) that ride request paths as plain ints — they
work even when tracing is off, so flag-off call sites need no branches.

What the process did before ``configure()`` — imports, the backend's
start, building the trainer, the first dispatch's compilation — is kept by
``obs.startup`` whether tracing is on or off; :meth:`Tracer.events` merges
that record in, so an export holds it on the same timeline whichever tracer
was installed when the phases ran.

Child processes inherit the configuration through ``DEEPFM_TPU_TRACE*``
env vars (set by :func:`configure`, read by :func:`configure_from_env`);
each process exports its own ``trace-<pid>.json`` and :func:`merge`
concatenates them into one file.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import resource
import threading
import time
from typing import Deque, Dict, Iterable, List, Optional, Tuple, Union

from . import startup

MODES = ("off", "ring", "full")
DEFAULT_CAPACITY = 65536

ENV_MODE = "DEEPFM_TPU_TRACE"
ENV_DIR = "DEEPFM_TPU_TRACE_DIR"
ENV_BUFFER = "DEEPFM_TPU_TRACE_BUFFER"

#: The pulse's beat, the lateness that makes a beat a ``host.stall``, and
#: the thread's name.
PULSE_MS = 5
STALL_MS = 20
PULSE_THREAD = "trace-pulse"


class _NullSpan:
    """Shared no-op span: what ``span()`` hands out when tracing is off."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def add(self, **attrs) -> "_NullSpan":
        return self


_NULL = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, args: Dict):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._t0 = 0

    def __enter__(self) -> "_Span":
        self._t0 = time.time_ns()
        return self

    def add(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. rows after batching)."""
        self._args.update(attrs)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.time_ns()
        ev = {"name": self._name, "ph": "X", "ts": self._t0 / 1e3,
              "dur": (t1 - self._t0) / 1e3, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if self._args:
            ev["args"] = self._args
        self._tracer._emit(ev)
        return False


class Tracer:
    """Ring- or list-buffered span recorder. Thread-safe; one per process."""

    def __init__(self, mode: str = "off",
                 capacity: int = DEFAULT_CAPACITY) -> None:
        if mode not in MODES:
            raise ValueError(f"trace mode must be one of {MODES}, got {mode!r}")
        self.mode = mode
        self.capacity = max(int(capacity), 1)
        self._lock = threading.Lock()
        self._buf: List[Dict] = []
        self._head = 0          # ring overwrite cursor (oldest event)
        self._dropped = 0       # ring wraparound overwrites, counted
        # host.gc events, written without the lock (see _emit_gc)
        self._gc_buf: Deque[Dict] = collections.deque(
            maxlen=self.capacity if mode == "ring" else None)
        self._gc_dropped = 0
        self._ids = itertools.count(1)

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    @property
    def dropped(self) -> int:
        return self._dropped + self._gc_dropped

    def _emit(self, ev: Dict) -> None:
        with self._lock:
            if self.mode == "ring" and len(self._buf) >= self.capacity:
                self._buf[self._head] = ev
                self._head = (self._head + 1) % self.capacity
                self._dropped += 1
            else:
                self._buf.append(ev)

    def _emit_gc(self, ev: Dict) -> None:
        """Record an event from inside the collector. The collection may
        have started on a thread that holds ``_lock`` (``events()`` and
        ``_emit`` allocate under it), so taking the lock here would hang
        that thread for good. ``deque.append`` is one atomic call, and
        collections never nest or overlap, so nothing else writes here."""
        if len(self._gc_buf) == self._gc_buf.maxlen:
            self._gc_dropped += 1
        self._gc_buf.append(ev)

    def span(self, name: str, **attrs) -> Union[_Span, _NullSpan]:
        if self.mode == "off":
            return _NULL
        return _Span(self, name, attrs)

    def begin(self, name: str, **attrs) -> Optional[Tuple[str, int]]:
        """Open an async span; finish it with :meth:`end` from ANY thread.
        Returns an opaque handle (None when tracing is off)."""
        if self.mode == "off":
            return None
        hid = next(self._ids)
        ev = {"name": name, "cat": name.split(".", 1)[0], "ph": "b",
              "id": hid, "ts": time.time_ns() / 1e3, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        self._emit(ev)
        return (name, hid)

    def end(self, handle: Optional[Tuple[str, int]], **attrs) -> None:
        if handle is None:
            return
        name, hid = handle
        ev = {"name": name, "cat": name.split(".", 1)[0], "ph": "e",
              "id": hid, "ts": time.time_ns() / 1e3, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        self._emit(ev)

    def complete(self, name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
        """A complete ("X") event whose times someone else took (JAX times
        its own compilations), on the calling thread."""
        if self.mode == "off":
            return
        ev = {"name": name, "ph": "X", "ts": t0_ns / 1e3,
              "dur": (t1_ns - t0_ns) / 1e3, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        self._emit(ev)

    def instant(self, name: str, **attrs) -> None:
        if self.mode == "off":
            return
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": time.time_ns() / 1e3, "pid": os.getpid(),
              "tid": threading.get_ident()}
        if attrs:
            ev["args"] = attrs
        self._emit(ev)

    def events(self) -> List[Dict]:
        """Chronological snapshot: what the buffer holds (after a ring's
        wraparound, the newest ``capacity`` events), the collector's
        ``host.gc`` events and, while tracing is on, the start-up record
        (``obs.startup``: stored there once, whichever tracer was installed
        when its phases ran), by start time."""
        with self._lock:
            out = list(self._buf)
        out.extend(self._gc_buf)
        if self.mode != "off":
            out.extend(startup.events())
        out.sort(key=lambda e: e["ts"])
        return out


# --------------------------------------------------------------------------
# Process-global tracer + module-level API (what call sites import).
# --------------------------------------------------------------------------

_tracer = Tracer()
_trace_dir = ""
_id_counter = itertools.count(1)


_gc_t0 = 0


def _on_gc(phase: str, info: Dict) -> None:
    """``gc.callbacks`` hook: one ``host.gc`` span per collection. The
    collector never nests, so one module-level slot holds the start time;
    the event goes in without the tracer's lock (``Tracer._emit_gc``)."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.time_ns()
    elif _gc_t0:
        t1 = time.time_ns()
        _tracer._emit_gc({
            "name": "host.gc", "ph": "X", "ts": _gc_t0 / 1e3,
            "dur": (t1 - _gc_t0) / 1e3, "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {"generation": info["generation"],
                     "collected": info["collected"]}})
        _gc_t0 = 0


def _hook_gc(on: bool) -> None:
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    if on:
        gc.callbacks.append(_on_gc)


# --------------------------------------------------------------------------
# The host pulse: a stall of the whole process as a span with its cause.
# --------------------------------------------------------------------------

STALL_CAUSES = ("host_cpu", "gil", "memory", "io", "frozen")
#: Of the counters' deltas, those a ``host.pulse`` carries.
_PULSE_KEYS = ("runq_ms", "cpu_ms", "steal_ms", "majflt", "psi_cpu_ms",
               "psi_mem_ms", "psi_io_ms")


def stall_cause(d: Dict) -> str:
    """Why a beat came ``d["late_ms"]`` late, from what the operating
    system counted meanwhile (the other keys of a ``host.stall``, each
    optional). ``host_cpu``: the pulse stood runnable without a CPU for
    most of it (``steal_ms`` / ``throttled_ms`` say whether the hypervisor
    or the cgroup's quota took it). ``gil``: it was not waiting for a CPU
    and the process used most of the lateness in CPU time, so a thread of
    ours held the interpreter (``busiest_thread`` is the suspect: a
    reading, not a proof). ``memory`` / ``io``: major faults or memory
    pressure, or I/O pressure, rose. ``frozen``: none of these; the
    process was stopped, or every thread slept. On a host without
    ``runq_ms`` (a sandboxed guest) a beat that stood runnable without a
    CPU reads ``frozen`` if the other threads stood too and ``gil`` if they
    ran; and where the process's native threads alone use half a core,
    ``gil`` wants a span of ours under the stall before it is believed."""
    half = d["late_ms"] / 2
    if d.get("runq_ms", 0.0) >= half:
        return "host_cpu"
    if d.get("cpu_ms", 0.0) >= half:
        return "gil"
    if d.get("majflt", 0) > 0 or d.get("psi_mem_ms", 0.0) >= half:
        return "memory"
    if d.get("psi_io_ms", 0.0) >= half:
        return "io"
    return "frozen"


def _read(path: str, size: int = 4096) -> bytes:
    fd = os.open(path, os.O_RDONLY)
    try:
        return os.read(fd, size)
    finally:
        os.close(fd)


def _cgroup_cpu_stat() -> Optional[str]:
    """The ``cpu.stat`` of this process's cgroup (v2, or v1's ``cpu``
    controller), else of the mount's root, else None."""
    rel = [""]
    try:
        for line in _read("/proc/self/cgroup").decode().splitlines():
            _, controllers, path = line.split(":", 2)
            if not controllers or "cpu" in controllers.split(","):
                rel.insert(0, path.rstrip("/"))
    except (OSError, ValueError):
        return None
    for path in rel:
        for root in ("/sys/fs/cgroup", "/sys/fs/cgroup/cpu",
                     "/sys/fs/cgroup/cpu,cpuacct"):
            if os.path.exists(f"{root}{path}/cpu.stat"):
                return f"{root}{path}/cpu.stat"
    return None


class _OsCounters:
    """What the operating system counts about this process and its
    machine, as running totals in ms (or events) under the keys of the
    spans. Built on the pulse thread: ``/proc/thread-self`` is the thread
    that opens it. A source that cannot be read is left out."""

    def __init__(self) -> None:
        try:    # "<on-CPU ns> <run-queue wait ns> <timeslices>"
            self._schedstat = os.open("/proc/thread-self/schedstat",
                                      os.O_RDONLY)
        except OSError:
            self._schedstat = None
        self._tick_ms = 1e3 / os.sysconf("SC_CLK_TCK")
        self._cpu_stat = _cgroup_cpu_stat()

    def close(self) -> None:
        if self._schedstat is not None:
            os.close(self._schedstat)
            self._schedstat = None

    def cheap(self) -> Dict[str, float]:
        """Read every beat: this thread's run-queue wait and the process's
        CPU time, major faults and involuntary switches."""
        ru = resource.getrusage(resource.RUSAGE_SELF)
        out = {"cpu_ms": 1e3 * (ru.ru_utime + ru.ru_stime),
               "majflt": ru.ru_majflt, "nivcsw": ru.ru_nivcsw}
        if self._schedstat is not None:
            out["runq_ms"] = int(
                os.pread(self._schedstat, 64, 0).split()[1]) / 1e6
        return out

    def dear(self) -> Tuple[Dict[str, float], Dict[int, Tuple[float, str]]]:
        """Read once a second and under a stall: the machine's stolen
        time, the pressure totals, the cgroup's throttled time; and each
        task's CPU time with its ``comm``."""
        out: Dict[str, float] = {}
        try:    # "cpu  user nice system idle iowait irq softirq steal ..."
            out["steal_ms"] = int(_read("/proc/stat", 256).split()[8]) \
                * self._tick_ms
        except (OSError, ValueError, IndexError):
            pass
        for key, name in (("psi_cpu_ms", "cpu"), ("psi_mem_ms", "memory"),
                          ("psi_io_ms", "io")):
            try:    # "some avg10=0.00 avg60=0.00 avg300=0.00 total=<us>"
                some = _read(f"/proc/pressure/{name}").split(b"\n")[0]
                out[key] = int(some.rsplit(b"=", 1)[1]) / 1e3
            except (OSError, ValueError, IndexError):
                pass
        if self._cpu_stat is not None:
            try:
                for line in _read(self._cpu_stat).decode().splitlines():
                    if line.startswith("throttled_usec "):      # v2
                        out["throttled_ms"] = int(line.split()[1]) / 1e3
                    elif line.startswith("throttled_time "):    # v1, ns
                        out["throttled_ms"] = int(line.split()[1]) / 1e6
            except (OSError, ValueError, IndexError):
                pass
        tasks: Dict[int, Tuple[float, str]] = {}
        try:
            tids = os.listdir("/proc/self/task")
        except OSError:
            tids = []
        for tid in tids:
            try:    # "tid (comm) state ... utime stime ...", fields 14, 15
                head, _, rest = _read(f"/proc/self/task/{tid}/stat",
                                      1024).rpartition(b")")
                fields = rest.split()
                tasks[int(tid)] = (
                    (int(fields[11]) + int(fields[12])) * self._tick_ms,
                    head.partition(b"(")[2].decode(errors="replace"))
            except (OSError, ValueError, IndexError):
                pass    # the thread ended between the listing and the read
        return out, tasks


def _deltas(now: Dict[str, float], then: Dict[str, float]) -> Dict:
    return {k: round(v - then[k], 3) for k, v in now.items() if k in then}


def _busiest(now: Dict[int, Tuple[float, str]],
             then: Dict[int, Tuple[float, str]]) -> Dict:
    """The task that used most CPU between two readings, by its Python
    thread's name where it has one, else the task's ``comm``."""
    used = {tid: ms - then.get(tid, (0.0, ""))[0]
            for tid, (ms, _) in now.items()}
    if not used:
        return {}
    tid = max(used, key=used.get)
    names = {t.native_id: t.name for t in threading.enumerate()}
    return {"busiest_thread": names.get(tid, now[tid][1]),
            "busiest_cpu_ms": round(used[tid], 3)}


class _Pulse:
    """The pulse thread of one tracer; see the module's docstring."""

    def __init__(self, tracer: Tracer) -> None:
        self._tracer = tracer
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name=PULSE_THREAD,
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _run(self) -> None:
        counters = _OsCounters()
        try:
            self._beat(counters)
        finally:
            counters.close()

    def _beat(self, counters: _OsCounters) -> None:
        period, stall_ns = PULSE_MS * 1_000_000, STALL_MS * 1_000_000
        emit = self._tracer.complete
        # Baselines: cheap counters at the last beat and the last pulse,
        # dear ones at their last reading and the last pulse.
        beat = pulse = counters.cheap()
        pulse_at = time.time_ns()
        dear, tasks = counters.dear()
        pulse_dear = dear
        read_ns = time.time_ns() - pulse_at     # the shortest reading so far
        beats = late_max = 0
        due = time.time_ns() + period
        while not self._stop.is_set():
            wait = due - time.time_ns()
            if wait > 0:
                time.sleep(wait / 1e9)
            now = time.time_ns()
            late = now - due
            stalled = late > stall_ns
            pulse_due = now - pulse_at >= 1_000_000_000
            # The next deadline is set before this beat's own work (with
            # room for a reading of the dear counters), so a stall that
            # begins while the pulse is at work makes the next beat late.
            due += period
            if due <= now:      # beats were missed: count from here
                due = now + period
            if stalled or pulse_due:
                due = max(due, now + 2 * read_ns)
            cheap = counters.cheap()
            beats += 1
            late_max = max(late_max, late)
            if stalled or pulse_due:
                new_dear, new_tasks = counters.dear()
                read_ns = min(read_ns, time.time_ns() - now)
                if stalled:
                    args = {"late_ms": round(late / 1e6, 3),
                            **_deltas(cheap, beat), **_deltas(new_dear, dear),
                            **_busiest(new_tasks, tasks)}
                    emit("host.stall", now - late, now,
                         cause=stall_cause(args), **args)
                dear, tasks = new_dear, new_tasks
            if pulse_due:
                args = {**_deltas(cheap, pulse), **_deltas(dear, pulse_dear)}
                emit("host.pulse", pulse_at, now, beats=beats,
                     late_ms_max=round(late_max / 1e6, 3),
                     **{k: args[k] for k in _PULSE_KEYS if k in args})
                pulse, pulse_dear, pulse_at = cheap, dear, now
                beats = late_max = 0
            beat = cheap


_pulse: Optional[_Pulse] = None


def _run_pulse(on: bool) -> None:
    """One pulse while tracing is on, none while it is off."""
    global _pulse
    if _pulse is not None:
        _pulse.stop()
        _pulse = None
    if on:
        _pulse = _Pulse(_tracer)


def configure(mode: str, *, capacity: int = DEFAULT_CAPACITY,
              trace_dir: str = "", export_env: bool = True) -> None:
    """Install the process-global tracer. With ``export_env`` (default) the
    settings also land in ``DEEPFM_TPU_TRACE*`` so spawned child processes
    (input workers, drill trainer) inherit them via
    :func:`configure_from_env`."""
    global _tracer, _trace_dir
    _tracer = Tracer(mode, capacity)
    _trace_dir = trace_dir or ""
    _hook_gc(_tracer.enabled)
    _run_pulse(_tracer.enabled)
    if export_env:
        os.environ[ENV_MODE] = mode
        os.environ[ENV_BUFFER] = str(int(capacity))
        if trace_dir:
            os.environ[ENV_DIR] = trace_dir
        else:
            os.environ.pop(ENV_DIR, None)


def configure_from_env() -> None:
    """Child-process entry: adopt the parent's trace settings (no-op when
    the parent never configured tracing)."""
    mode = os.environ.get(ENV_MODE, "off")
    if mode == "off":
        return
    try:
        capacity = int(os.environ.get(ENV_BUFFER, DEFAULT_CAPACITY))
    except ValueError:
        capacity = DEFAULT_CAPACITY
    configure(mode, capacity=capacity,
              trace_dir=os.environ.get(ENV_DIR, ""), export_env=False)


def reset() -> None:
    """Back to off + empty buffers, the start-up record's too (tests)."""
    global _tracer, _trace_dir
    _tracer = Tracer()
    _trace_dir = ""
    _hook_gc(False)
    _run_pulse(False)
    startup.reset()
    for k in (ENV_MODE, ENV_DIR, ENV_BUFFER):
        os.environ.pop(k, None)


def enabled() -> bool:
    return _tracer.enabled


def span(name: str, **attrs) -> Union[_Span, _NullSpan]:
    return _tracer.span(name, **attrs)


def begin(name: str, **attrs) -> Optional[Tuple[str, int]]:
    return _tracer.begin(name, **attrs)


def end(handle: Optional[Tuple[str, int]], **attrs) -> None:
    _tracer.end(handle, **attrs)


def complete(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    _tracer.complete(name, t0_ns, t1_ns, **attrs)


def instant(name: str, **attrs) -> None:
    _tracer.instant(name, **attrs)


def dropped() -> int:
    """Events lost to the ring's wraparound and phases the start-up record
    had no room for."""
    return _tracer.dropped + startup.dropped()


def new_trace_id() -> int:
    """Mint a correlation id unique across the processes of one run
    (pid-tagged). Works with tracing off — call sites never branch."""
    return (os.getpid() << 20) | (next(_id_counter) & 0xFFFFF)


def export(path: Optional[str] = None) -> Optional[str]:
    """Write this process's events as a Chrome trace JSON; returns the path
    (None when tracing is off). Default path: ``<trace_dir>/trace-<pid>.json``."""
    if not _tracer.enabled:
        return None
    pid = os.getpid()
    if path is None:
        d = _trace_dir or "."
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"trace-{pid}.json")
    events = [{"name": "process_name", "ph": "M", "pid": pid,
               "args": {"name": f"deepfm_tpu[{pid}]"}}]
    events.extend(_tracer.events())
    doc = {"traceEvents": events,
           "otherData": {"pid": pid, "mode": _tracer.mode,
                         "dropped_spans": dropped()}}
    tmp = f"{path}.tmp-{pid}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def merge(src: Union[str, Iterable[str]], out: str) -> str:
    """Concatenate per-process trace files (a directory of
    ``trace-*.json`` or an explicit path list) into one loadable trace;
    per-process drop counts are summed into ``otherData``."""
    if isinstance(src, str):
        paths = sorted(
            os.path.join(src, f) for f in os.listdir(src)
            if f.startswith("trace-") and f.endswith(".json"))
    else:
        paths = list(src)
    events: List[Dict] = []
    total_dropped = 0
    pids: List[int] = []
    for p in paths:
        with open(p) as f:
            doc = json.load(f)
        events.extend(doc.get("traceEvents", []))
        other = doc.get("otherData", {})
        total_dropped += int(other.get("dropped_spans", 0))
        if "pid" in other:
            pids.append(int(other["pid"]))
    doc = {"traceEvents": events,
           "otherData": {"merged_from": len(paths), "pids": pids,
                         "dropped_spans": total_dropped}}
    tmp = f"{out}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out)
    return out
