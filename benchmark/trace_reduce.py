"""From the profiler's trace to numbers. `read_xplane` turns an
`.xplane.pb` into plain lists (planes -> lines -> [name, start_ns,
dur_ns]); `reduce` works on those lists alone, so a small recorded trace
in that form checks every number here by hand (tests/benchmark).

What it reads, on a TPU trace as JAX 0.9 writes it:
- device planes `/device:TPU:<n>`: the line `XLA Ops` (one event per
  executed operation; a `while` spans its body's operations, so the sum
  of the line counts those twice and only the union is busy time) gives
  busy time and the operations' names, the line `XLA Modules` one event
  per executed program, the line `Async XLA Ops` the spans of copies and
  collectives in flight beside the stream;
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
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"  # spans of operations in flight beside the stream (copies, collectives)
ALLREDUCE = re.compile(r"all-reduce|all_reduce|AllReduce", re.I)
WINDOW_SPAN = "bench_window"  # a host span that, where present, is the window
MAX_GAPS_ATTRIBUTED = 400
MIN_GAP_NS = 20_000


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events]
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
        for name, s, d in ln["events"]:
            if name == drop or s + d <= lo or s >= hi:
                continue
            if whole and (s < lo or s + d > hi):
                continue
            a, b = max(s, lo), min(s + d, hi)
            events.append([name, a, b - a])
        lines.append({"name": ln["name"], "events": events})
    return {"name": plane["name"], "lines": lines}


def short_name(name: str) -> str:
    """An operation's name without the HLO text after it."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def reduce(events: dict, chips: int) -> dict:
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
    for p in devices:
        ops = _line(p, OPS_LINE)["events"]
        busy_iv = union(_intervals(ops))
        busy.append(length(busy_iv))
        for name, _, dur in ops:
            key = short_name(name)
            op_time[key] = op_time.get(key, 0.0) + dur / len(devices)
        mods = _line(p, MODULES_LINE)
        if mods is not None:
            per_mod: Dict[str, List[int]] = {}
            for name, _, dur in mods["events"]:
                per_mod.setdefault(name, []).append(dur)
            durs = per_mod.get(step_name, [])
            step_busy.append(sum(durs))
            step_counts.append(len(durs))
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
    idle_by = attribute_gaps(gaps, hosts)

    n = len(devices)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "chips_traced": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "step_name": step_name,
        "step_busy_s": (sum(step_busy) / len(step_busy) / 1e9) if step_busy else 0.0,
        "step_count": (sum(step_counts) / len(step_counts)) if step_counts else 0,
        "allreduce_exposed_s": (sum(exposed) / n / 1e9) if any_allreduce else None,
        "breakdown": {
            "device_ops": [[k[:120], v / 1e9] for k, v in top_ops],
            "idle_gaps": [[k[:120], v / 1e9] for k, v in top_gaps],
        },
    }


def attribute_gaps(gaps: np.ndarray, hosts: List[dict]) -> Dict[str, float]:
    """Seconds of device 0's idle gaps by the host span that covers each
    most: `<thread>:<span>` -> ns. Only the longest gaps are looked up;
    the rest go under `(short gaps)`."""
    out: Dict[str, float] = {}
    if len(gaps) == 0:
        return out
    names: List[str] = []
    starts: List[int] = []
    ends: List[int] = []
    for p in hosts:
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                names.append(f"{ln['name']}:{name}")
                starts.append(s)
                ends.append(s + d)
    order = np.argsort(-(gaps[:, 1] - gaps[:, 0]))
    looked = order[:MAX_GAPS_ATTRIBUTED]
    rest = order[MAX_GAPS_ATTRIBUTED:]
    if len(rest):
        out["(short gaps)"] = float((gaps[rest, 1] - gaps[rest, 0]).sum())
    if not names:
        out["(no host span)"] = float((gaps[looked, 1] - gaps[looked, 0]).sum())
        return out
    st = np.array(starts, dtype=np.int64)
    en = np.array(ends, dtype=np.int64)
    dur = en - st
    for g in looked:
        a, b = gaps[g]
        overlap = np.minimum(en, b) - np.maximum(st, a)
        cand = np.flatnonzero(2 * overlap >= (b - a))
        if len(cand):
            key = names[int(cand[np.argmin(dur[cand])])]
        elif overlap.max() > 0:
            key = names[int(np.argmax(overlap))]
        else:
            key = "(no host span)"
        out[key] = out.get(key, 0.0) + float(b - a)
    return out
