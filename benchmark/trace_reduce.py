"""From the profiler's trace to numbers. `read_xplane` turns an
`.xplane.pb` into plain lists (planes -> lines -> [name, start_ns,
dur_ns], and on a device's `XLA Ops` line a fourth element, the
operation's `op_name` path or None); `reduce` works on those lists alone,
so a small recorded trace in that form checks every number here by hand
(tests/benchmark).

What it reads, on a TPU trace as JAX 0.9 writes it:
- device planes `/device:TPU:<n>`: the line `XLA Ops` (one event per
  executed operation; a `while` spans its body's operations, so the sum
  of the line counts those twice and only the union is busy time) gives
  busy time and the operations' names, the line `XLA Modules` one event
  per executed program, the line `Async XLA Ops` the spans of copies and
  collectives in flight beside the stream. An operation's `op_name`
  (`jit(f)/loss/jvp(Net)/core/lstm/while/body/dot_general:`, what
  `jax.named_scope` and the module names put there) is the `tf_op` stat
  of the event's metadata, which `jax.profiler.ProfileData` does not give:
  the file is read as the raw `XSpace` protobuf;
- host planes (`/host:CPU`): one line per thread, with the runtime's and,
  with the Python tracer on, every Python function's span.

Busy is the union of the operation intervals of a device; idle share is
1 - busy / window, where the window is the host span `bench_window` that
the harness writes around it (starting and stopping the profiler idles the
device and is no part of the window), or the span of everything traced. The
step program is the module that took most device time. An all-reduce is
exposed while no other operation runs on its device. A gap in device 0's
busy union is put down to the shortest host span that covers at least
half of it (so a thread's root frame wins only where nothing inside it was
traced), or failing that to the span that overlaps it most.

Device time by scope (`scope_self_s`), of the step program's operations:
an operation's self time is the time in which it is the innermost
operation running (a `while` less the operations it spans, so a body is
counted once, and the self times of a device add up to its busy union); it
goes to the innermost component of the operation's path that is one of the
scopes the configuration declares (`scopes/<config>.json`), or to `""`.
The table is None, not wrong, where the declared scopes carry under
`MIN_SCOPED` of the step's time: a trace without paths, or an executable
that another tree compiled.
"""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Dict, List, Optional, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"  # spans of operations in flight beside the stream (copies, collectives)
ALLREDUCE = re.compile(r"all-reduce|all_reduce|AllReduce", re.I)
WINDOW_SPAN = "bench_window"  # a host span that, where present, is the window
MAX_GAPS_ATTRIBUTED = 400
MIN_GAP_NS = 20_000
LONGEST_GAPS = 5
OP_NAME_STAT = "tf_op"  # the TPU runtime's name for an operation's `op_name`
MIN_SCOPED = 0.9  # of the step's device time, or there is no table by scope


def _xplane_pb2():
    """The `XSpace` message classes. They ship inside the tensorflow
    package; loaded by path, so that tensorflow itself is not imported."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise ImportError("no tensorflow package to take xplane_pb2 from")
    path = os.path.join(list(spec.submodule_search_locations)[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("benchmark_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_xplane(path: str) -> dict:
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for plane in space.planes:
        meta = plane.event_metadata
        paths: Dict[int, str] = {}  # event metadata id -> op_name
        if DEVICE_PLANE.match(plane.name):
            stat_names = plane.stat_metadata
            for mid, m in meta.items():
                for st in m.stats:
                    if stat_names[st.metadata_id].name == OP_NAME_STAT:
                        paths[mid] = st.str_value or stat_names[st.ref_value].name
        lines = []
        for line in plane.lines:
            t0 = line.timestamp_ns
            # start and duration as `jax.profiler.ProfileData` gives them
            events = [[meta[ev.metadata_id].name, int(t0 + ev.offset_ps / 1000.0),
                       int(ev.duration_ps / 1000.0)] for ev in line.events]
            if paths and line.name == OPS_LINE:
                for out, ev in zip(events, line.events):
                    out.append(paths.get(ev.metadata_id))
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, merged [start, end) intervals of an [n, 2] array."""
    if len(intervals) == 0:
        return np.zeros((0, 2), dtype=np.int64)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return np.array(out, dtype=np.int64)


def length(iv: np.ndarray) -> int:
    return int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The part of merged intervals `a` that merged intervals `b` do not cover."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return np.array(out, dtype=np.int64).reshape(-1, 2)


def _intervals(events) -> np.ndarray:
    if not events:
        return np.zeros((0, 2), dtype=np.int64)
    a = np.array([[e[1], e[1] + e[2]] for e in events], dtype=np.int64)
    return a


def _line(plane: dict, name: str) -> Optional[dict]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def _clip_plane(plane: dict, lo: int, hi: int, drop: Optional[str] = None) -> dict:
    """The plane's events cut to [lo, hi): what lies outside goes, what
    straddles an end is shortened; a module that straddles is dropped
    whole (a step counts only if all of it ran inside)."""
    lines = []
    for ln in plane["lines"]:
        whole = ln["name"] == MODULES_LINE
        events = []
        for name, s, d, *rest in ln["events"]:
            if name == drop or s + d <= lo or s >= hi:
                continue
            if whole and (s < lo or s + d > hi):
                continue
            a, b = max(s, lo), min(s + d, hi)
            events.append([name, a, b - a, *rest])
        lines.append({"name": ln["name"], "events": events})
    return {"name": plane["name"], "lines": lines}


def short_name(name: str) -> str:
    """An operation's name without the HLO text after it."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def self_times(ops: Sequence) -> np.ndarray:
    """Each event's self time in ns: the time in which it is the innermost
    (latest started) event of its line still running. Where events nest, as
    a `while` and its body do, that is its duration less what it spans;
    the self times of a line add up to the length of its union."""
    own = np.zeros(len(ops), dtype=np.int64)
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    stack: List[int] = []
    cursor = 0

    def close_until(t):
        nonlocal cursor
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= t:
            i = stack.pop()
            end = ops[i][1] + ops[i][2]
            if end > cursor:
                own[i] += end - cursor
                cursor = end

    for i in order:
        start = ops[i][1]
        close_until(start)
        if stack and start > cursor:
            own[stack[-1]] += start - cursor
        cursor = max(cursor, start)
        stack.append(i)
    close_until(float("inf"))
    return own


def scope_of(path: Optional[str], declared: Sequence[str]) -> str:
    """The innermost component of an operation's `op_name` path that is a
    declared scope, `""` where there is none. The path's last component is
    the primitive's own name and no scope."""
    if not path:
        return ""
    for part in reversed(path.rsplit(":", 1)[0].split("/")[:-1]):
        if part in declared:
            return part
    return ""


def _scope_self(ops: Sequence, step_iv: np.ndarray, declared: Sequence[str]) -> Dict[str, int]:
    """Self time in ns by scope, of the operations of one device that start
    inside one of the step program's runs `step_iv` ([n, 2], sorted)."""
    out = {name: 0 for name in ("", *declared)}
    if not len(step_iv) or not ops:
        return out
    own = self_times(ops)
    starts = np.array([e[1] for e in ops], dtype=np.int64)
    at = np.searchsorted(step_iv[:, 0], starts, side="right") - 1
    inside = (at >= 0) & (starts < step_iv[np.maximum(at, 0), 1])
    scopes: Dict[Optional[str], str] = {}
    for i in np.flatnonzero(inside & (own > 0)):
        path = ops[i][3] if len(ops[i]) > 3 else None
        if path not in scopes:
            scopes[path] = scope_of(path, declared)
        out[scopes[path]] += int(own[i])
    return out


def reduce(events: dict, chips: int, scopes: Optional[Sequence[str]] = None) -> dict:
    """`scopes`: the layer scopes that the configuration declares, for
    `scope_self_s`; without them there is no such table."""
    devices = sorted(
        (p for p in events["planes"] if DEVICE_PLANE.match(p["name"])),
        key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)),
    )
    devices = [p for p in devices if _line(p, OPS_LINE) is not None][:chips]
    if not devices:
        names = [(p["name"], [ln["name"] for ln in p["lines"]]) for p in events["planes"]]
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in the trace: {names}")
    hosts = [p for p in events["planes"] if p["name"].startswith("/host:")]

    lo = min(ev[1] for p in devices + hosts for ln in p["lines"] for ev in ln["events"])
    hi = max(ev[1] + ev[2] for p in devices + hosts for ln in p["lines"] for ev in ln["events"])
    marks = [ev for p in hosts for ln in p["lines"] for ev in ln["events"] if ev[0] == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0][1], marks[0][1] + marks[0][2]
        devices = [_clip_plane(p, lo, hi) for p in devices]
        hosts = [_clip_plane(p, lo, hi, drop=WINDOW_SPAN) for p in hosts]
    window_ns = hi - lo

    busy, step_busy, step_counts, exposed = [], [], [], []
    op_time: Dict[str, float] = {}
    any_allreduce = False
    totals: Dict[str, int] = {}
    for p in devices:
        for name, _, dur in (_line(p, MODULES_LINE) or {"events": []})["events"]:
            totals[name] = totals.get(name, 0) + dur
    step_name = max(totals, key=totals.get) if totals else None
    by_scope: Dict[str, float] = {}
    for p in devices:
        ops = _line(p, OPS_LINE)["events"]
        busy_iv = union(_intervals(ops))
        busy.append(length(busy_iv))
        for ev in ops:
            key = short_name(ev[0])
            op_time[key] = op_time.get(key, 0.0) + ev[2] / len(devices)
        mods = _line(p, MODULES_LINE)
        if mods is not None:
            runs = [e for e in mods["events"] if e[0] == step_name]
            step_busy.append(sum(e[2] for e in runs))
            step_counts.append(len(runs))
            if scopes:
                step_iv = _intervals(sorted(runs, key=lambda e: e[1]))
                for k, ns in _scope_self(ops, step_iv, scopes).items():
                    by_scope[k] = by_scope.get(k, 0.0) + ns / len(devices) / 1e9
        ar = [e for e in ops if ALLREDUCE.search(e[0])]
        asyn = _line(p, ASYNC_LINE)
        if asyn is not None:
            ar += [e for e in asyn["events"] if ALLREDUCE.search(e[0])]
        if ar:
            any_allreduce = True
            others = union(_intervals([e for e in ops if not ALLREDUCE.search(e[0])]))
            exposed.append(length(subtract(union(_intervals(ar)), others)))
        else:
            exposed.append(0)

    gaps = subtract(np.array([[lo, hi]], dtype=np.int64), union(_intervals(_line(devices[0], OPS_LINE)["events"])))
    gaps = gaps[(gaps[:, 1] - gaps[:, 0]) >= MIN_GAP_NS] if len(gaps) else gaps
    covered = cover_gaps(gaps, hosts)
    idle_by: Dict[str, float] = {}
    for (a, b), key in zip(gaps, covered):
        idle_by[key] = idle_by.get(key, 0.0) + float(b - a)
    longest = sorted(range(len(gaps)), key=lambda g: -(gaps[g][1] - gaps[g][0]))[:LONGEST_GAPS]

    n = len(devices)
    step_busy_s = (sum(step_busy) / len(step_busy) / 1e9) if step_busy else 0.0
    scoped_s = sum(v for k, v in by_scope.items() if k)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips_traced": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "step_name": step_name,
        "step_busy_s": step_busy_s,
        "step_count": (sum(step_counts) / len(step_counts)) if step_counts else 0,
        "allreduce_exposed_s": (sum(exposed) / n / 1e9) if any_allreduce else None,
        # seconds over the window's whole steps, mean over the chips, as `step_busy_s` is
        "scope_self_s": by_scope if step_busy_s and scoped_s >= MIN_SCOPED * step_busy_s else None,
        # device 0's longest gaps: [seconds into the window, seconds, covering host span]
        "longest_gaps": [[(int(gaps[g][0]) - lo) / 1e9, int(gaps[g][1] - gaps[g][0]) / 1e9,
                          covered[g][:120]] for g in longest],
        "breakdown": {
            "device_ops": [[k[:120], v / 1e9] for k, v in top_ops],
            "idle_gaps": [[k[:120], v / 1e9] for k, v in top_gaps],
        },
    }


def scope_ms(trace: Optional[dict], scope: str) -> Optional[float]:
    """For a metric's reader: the device's self time in `scope`, ms a step."""
    if not trace or not trace["step_count"] or scope not in (trace["scope_self_s"] or {}):
        return None
    return 1e3 * trace["scope_self_s"][scope] / trace["step_count"]


def cover_gaps(gaps: np.ndarray, hosts: List[dict]) -> List[str]:
    """For each of device 0's idle gaps the host span that covers it most,
    as `<thread>:<span>`. Only the longest gaps are looked up; the rest
    go under `(short gaps)`."""
    if len(gaps) == 0:
        return []
    names: List[str] = []
    starts: List[int] = []
    ends: List[int] = []
    for p in hosts:
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                names.append(f"{ln['name']}:{name}")
                starts.append(s)
                ends.append(s + d)
    out = ["(short gaps)"] * len(gaps)
    looked = np.argsort(-(gaps[:, 1] - gaps[:, 0]), kind="stable")[:MAX_GAPS_ATTRIBUTED]
    if not names:
        for g in looked:
            out[g] = "(no host span)"
        return out
    st = np.array(starts, dtype=np.int64)
    en = np.array(ends, dtype=np.int64)
    dur = en - st
    for g in looked:
        a, b = gaps[g]
        overlap = np.minimum(en, b) - np.maximum(st, a)
        cand = np.flatnonzero(2 * overlap >= (b - a))
        if len(cand):
            out[g] = names[int(cand[np.argmin(dur[cand])])]
        elif overlap.max() > 0:
            out[g] = names[int(np.argmax(overlap))]
        else:
            out[g] = "(no host span)"
    return out
