"""One span primitive for the learner process, on the device trace's clock.

    with span("publish.serialize", version=v):
        frame = serialize_weights(...)

A span does three things:

1. It enters `jax.profiler.TraceAnnotation(name, **ids)`. While a
   profiler session is open (the benchmark's traced run, an operator's
   POST /profile) the span lands in the xplane on the line of the thread
   that did the work, on the same clock as the device's operations,
   nested under whatever span encloses it on that thread. With no session
   open this is TraceMe's inactive path. "Tracing on" means a profiler
   session and nothing else.
2. It always adds (count, seconds) under `name` to one process-wide
   table. `scalars()` gives the table as cumulative
   `span_<name>_s_total` / `span_<name>_n_total` (a `.` in the name
   becomes `_` in the key); the learner emits them with every metrics
   window.
3. Where a flight recorder is installed (`mirror_to`, --obs.enabled) a
   closed span of `MIRROR_MIN_S` or more is mirrored into its ring, so a
   crash dump holds the last stalls (a publish's legs, a metrics sync,
   whatever a stall held up) beside the ring's rare events, and the
   routine spans of every step do not push those out.

Each name belongs to one thread: an entry is one tuple that its single
writer rebinds, so no lock is taken and a reader never sees a count
without its seconds. Spans are per batch, step, publish or pop, never
per frame.
"""

from __future__ import annotations

import ctypes
import time
from typing import Dict

import jax

MIRROR_MIN_S = 0.1  # longer than a step of any shipped policy; shorter than any stall worth a dump

_table: Dict[str, tuple] = {}  # name -> (count, total_ns)
_recorder = [None]  # the process's FlightRecorder, where one exists
_compiles = [0, 0.0]  # programs compiled or loaded, seconds making them
_listening = [False]

# jax.monitoring (jax 0.9.0): every program this process makes fires the
# three duration events below once, the last also when the persistent
# cache answers.
_COMPILE_DURATIONS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def add(name: str, ns: int) -> None:
    """Count one closed span of `ns` nanoseconds under `name` (for a
    duration that is no timeline span: submit on one thread, sent on
    another)."""
    n, total = _table.get(name, (0, 0))
    _table[name] = (n + 1, total + ns)


class span:
    __slots__ = ("name", "ids", "_annotation", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        ns = time.perf_counter_ns() - self._t0
        self._annotation.__exit__(*exc)
        add(self.name, ns)
        recorder = _recorder[0]
        if recorder is not None and ns >= MIRROR_MIN_S * 1e9:
            recorder.record(self.name, t=time.time() - ns / 1e9, ms=ns / 1e6, **self.ids)


# The timeline alone, no table entry: for what the program already sums
# under an older name (the lane's wait and put and the loop's take are
# time_wait_batch_s, time_device_put_s and pipeline_device_idle_s).
timeline = jax.profiler.TraceAnnotation


def mirror_to(recorder) -> None:
    """Mirror closed spans of `MIRROR_MIN_S` or more into `recorder` (a
    FlightRecorder), or into nothing (None)."""
    _recorder[0] = recorder


def count_compiles() -> None:
    """Register, once in a process, the jax.monitoring listener behind
    `compile_count_total` and `compile_s_total`."""
    if _listening[0]:
        return
    _listening[0] = True

    def on_duration(event: str, seconds: float, **_) -> None:
        if event in _COMPILE_DURATIONS:
            _compiles[1] += seconds
            if event == _COMPILE_DURATIONS[-1]:
                _compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def scalars() -> Dict[str, float]:
    """The table as scalars, and the compile counters where
    `count_compiles` has run."""
    out: Dict[str, float] = {}
    for name, (n, total) in list(_table.items()):
        key = "span_" + name.replace(".", "_")
        out[key + "_n_total"] = float(n)
        out[key + "_s_total"] = total / 1e9
    if _listening[0]:
        out["compile_count_total"] = float(_compiles[0])
        out["compile_s_total"] = float(_compiles[1])
    return out


def name_thread(name: str) -> None:
    """Give the calling thread its OS name (prctl PR_SET_NAME, 15
    characters), which the profiler takes for the thread's line in the
    xplane. Does nothing where there is no prctl."""
    try:
        prctl = ctypes.CDLL(None, use_errno=True).prctl
    except (OSError, AttributeError):
        return
    prctl.argtypes = [ctypes.c_int, ctypes.c_char_p] + [ctypes.c_ulong] * 3
    prctl.restype = ctypes.c_int
    prctl(15, name.encode()[:15], 0, 0, 0)
