"""Learner COMPUTE observability: step-phase timing, recompile sentinel,
MFU accounting, on-demand profiler capture.

PR 2 made the pipeline legible (where a rollout spends its time); the
learner's compute was still a black box — a silent XLA recompile, a
shrinking device/host ratio, or a stalled loop all looked identical on
the scrape surface. This module decomposes the steps/s headline into
causes:

- StepPhaseTimer   every learner iteration split into
                   fetch / pack / h2d / device_step / host wall time.
                   Exists only under --obs.enabled + --obs.step_phases;
                   the disabled path constructs nothing. The prefetch
                   lane records its own fetch/pack/h2d, the loop thread
                   reports the exposed wait/residual/host, and the
                   pipeline_* family carries the overlap accounting
                   with no per-step fence on the loop.
- RecompileSentinel wraps the jitted train step, hashes the abstract
                   avals + treedef of every call, counts signatures
                   beyond the first as recompiles, records compile wall
                   time, and dumps the offending shape-diff to the
                   flight recorder. Steady-state training must hold
                   compute_recompiles_total at 0 — any increment is a
                   batch-shape bug upstream.
- MfuAccountant    cumulative model-FLOPs utilization from the
                   ops/flops.py analytic cost model against the
                   per-platform peak table (TPU only; no peak entry →
                   no compute_mfu, achieved FLOP/s still reported).
- ProfileCapture   on-demand jax.profiler.trace windows for the obs
                   HTTP server's POST /profile?seconds=N: the one way
                   to open a profiler session on a running learner.

Everything logs through the existing MetricsLogger stream under the
compute_* names documented in obs/registry.py.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

_log = logging.getLogger(__name__)


# --------------------------------------------------------------- phases


class StepPhaseTimer:
    """Per-iteration wall-time decomposition of the learner loop.

    The host side of batch N+1 runs on the prefetch lane WHILE the
    device executes step N, so fencing the loop per step would destroy
    exactly what it measures. The accounting is two lanes:

    - the LOOP lane (add()/step(), single writer: the learner loop
      thread, which also calls window_scalars() at each metrics window;
      the scrape thread reads the RESULT via MetricsLogger.latest(),
      never this object):
        fetch        the loop's wait for a prefetched batch (the
                     exposed, un-hidden host time — the device-idle
                     upper bound)
        pack, h2d    0 here (the lane pays them)
        device_step  the UNFENCED residual (the in-flight device window
                     from the loop's clock)
        host         publish dispatch / checkpoint work
      The phases tile the wall by construction. Empty-wait retries
      record fetch time with no closing step(), so a STARVED window's
      fetch mean can exceed its wall mean — starvation is exactly when
      that should read loud.
    - the PREFETCH lane records its own fetch / pack / h2d wall via
      add_lane() (h2d FENCED there, so it is the real transfer time,
      not the dispatch time) — called from the lane thread, so those
      sums live under a lock — and window_scalars() reports them as the
      ``pipeline_*`` family: per-phase means, ``pipeline_prefetch_s``
      (lane busy per step), ``pipeline_device_idle_s`` (the exposed loop
      wait), and ``pipeline_overlap_ratio`` (share of lane work hidden
      behind the device step).
    """

    PHASES = ("fetch", "pack", "h2d", "device_step", "host")
    LANE_PHASES = ("fetch", "pack", "h2d")

    def __init__(self):
        self._sums: Dict[str, float] = dict.fromkeys(self.PHASES, 0.0)
        self._wall = 0.0
        self._steps = 0
        # Prefetch-lane sums: written by the lane thread, read by the
        # loop thread at window close — the one cross-thread surface, so
        # it gets its own lock (a handful of acquisitions per step
        # against a multi-ms step).
        self._lane_lock = threading.Lock()
        self._lane_sums: Dict[str, float] = dict.fromkeys(self.LANE_PHASES, 0.0)

    def add(self, phase: str, seconds: float) -> None:
        self._sums[phase] += max(float(seconds), 0.0)

    def add_lane(self, phase: str, seconds: float) -> None:
        """Prefetch-lane attribution: fetch/pack/h2d time the lane paid
        for a batch, hidden behind the device step. Called from the lane
        thread — the only writer of these sums."""
        with self._lane_lock:
            self._lane_sums[phase] += max(float(seconds), 0.0)

    def step(self, wall_seconds: float) -> None:
        """Close one loop iteration: its total wall time."""
        self._wall += max(float(wall_seconds), 0.0)
        self._steps += 1

    def window_scalars(self, reset: bool = True) -> Dict[str, float]:
        """Mean seconds per step for each loop phase over the window,
        the mean iteration wall, the fetch fraction (the watchdog's
        starvation signal) and the pipeline_* lane scalars. Resets the
        window by default (the learner logs once per metrics window,
        like its win_* accumulators)."""
        n = max(self._steps, 1)
        out = {f"compute_phase_{p}_s": self._sums[p] / n for p in self.PHASES}
        out["compute_phase_wall_s"] = self._wall / n
        if self._wall > 0:
            out["compute_phase_fetch_frac"] = self._sums["fetch"] / self._wall
        with self._lane_lock:
            lane = dict(self._lane_sums)
            if reset:
                self._lane_sums = dict.fromkeys(self.LANE_PHASES, 0.0)
        lane_total = sum(lane.values())
        exposed = self._sums["fetch"]  # loop wait for a prefetched batch
        for p in self.LANE_PHASES:
            out[f"pipeline_prefetch_{p}_s"] = lane[p] / n
        out["pipeline_prefetch_s"] = lane_total / n
        out["pipeline_device_idle_s"] = exposed / n
        out["pipeline_overlap_ratio"] = (
            max(0.0, min(1.0, 1.0 - exposed / lane_total)) if lane_total > 0 else 1.0
        )
        if reset:
            self._sums = dict.fromkeys(self.PHASES, 0.0)
            self._wall = 0.0
            self._steps = 0
        return out


# ------------------------------------------------------------- sentinel


def abstract_signature(tree) -> Tuple:
    """Hashable (treedef, per-leaf (shape, dtype)) summary of a pytree —
    exactly the cache key axes jax.jit re-traces on (plus sharding,
    which the learner pins via in_shardings). Non-array leaves hash by
    type, matching jit's weak-type/static treatment closely enough for a
    sentinel."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (
        treedef,
        tuple(
            (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", type(l).__name__)))
            for l in leaves
        ),
    )


def _described_leaves(tree) -> List[Tuple[str, Tuple, str]]:
    """[(path, shape, dtype)] — the human-readable form of the signature,
    computed only on cache misses (tree_flatten_with_path costs more than
    the plain flatten the hot path pays)."""
    import jax

    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "".join(str(p) for p in path)
        out.append(
            (name, tuple(getattr(leaf, "shape", ())), str(getattr(leaf, "dtype", type(leaf).__name__)))
        )
    return out


def signature_diff(old: List[Tuple], new: List[Tuple], limit: int = 12) -> List[str]:
    """Human-readable shape-diff between two described signatures — the
    payload of the flight-recorder recompile event. Bounded: a treedef
    change can differ in hundreds of leaves and the ring must not bloat."""
    old_map = {p: (s, d) for p, s, d in old}
    new_map = {p: (s, d) for p, s, d in new}
    diffs = []
    for p, (s, d) in new_map.items():
        if p not in old_map:
            diffs.append(f"+{p}: {s} {d}")
        elif old_map[p] != (s, d):
            os_, od = old_map[p]
            diffs.append(f"{p}: {os_} {od} -> {s} {d}")
    for p, (s, d) in old_map.items():
        if p not in new_map:
            diffs.append(f"-{p}: {s} {d}")
    if len(diffs) > limit:
        diffs = diffs[:limit] + [f"... {len(diffs) - limit} more"]
    return diffs


class RecompileSentinel:
    """Wraps a jitted callable; every call whose abstract signature was
    never seen before is counted as a compile (and, beyond the first, a
    RECOMPILE) and its wall time recorded — on a cache miss the call
    blocks through trace+lower+compile, so the call duration IS the
    compile wall time to within dispatch noise. Known signatures pay one
    tree_flatten + dict probe (~µs against a multi-ms train step).

    The shape-diff between the new signature and the previous one goes
    to the flight recorder (event "recompile"), so a dump answers WHICH
    leaf changed shape, not just that something did.
    """

    def __init__(self, fn, label: str = "train_step", recorder=None):
        self._fn = fn
        self._label = label
        self._recorder = recorder
        self._seen: Dict = {}  # signature -> described leaves
        self._last_desc: Optional[List[Tuple]] = None
        self.compiles = 0
        self.recompiles = 0
        self.compile_s = 0.0  # cumulative wall across all compiles
        self.last_compile_s = 0.0

    def __call__(self, *args):
        sig = abstract_signature(args)
        if sig in self._seen:
            return self._fn(*args)
        t0 = time.perf_counter()
        out = self._fn(*args)
        dt = time.perf_counter() - t0
        desc = _described_leaves(args)
        self.compiles += 1
        self.compile_s += dt
        self.last_compile_s = dt
        if self._last_desc is not None:
            self.recompiles += 1
            diff = signature_diff(self._last_desc, desc)
            _log.warning(
                "%s RECOMPILED (#%d, %.2fs): signature changed: %s",
                self._label,
                self.recompiles,
                dt,
                "; ".join(diff) or "<treedef-only change>",
            )
            if self._recorder is not None:
                self._recorder.record(
                    "recompile",
                    label=self._label,
                    n=self.recompiles,
                    compile_s=round(dt, 3),
                    diff=diff,
                )
        else:
            _log.info("%s compiled in %.2fs (first signature)", self._label, dt)
            if self._recorder is not None:
                self._recorder.record("compile", label=self._label, compile_s=round(dt, 3))
        self._seen[sig] = desc
        self._last_desc = desc
        return out

    def scalars(self) -> Dict[str, float]:
        return {
            "compute_recompiles_total": float(self.recompiles),
            "compute_compiles_total": float(self.compiles),
            "compute_compile_s": self.compile_s,
            "compute_last_compile_s": self.last_compile_s,
        }


# ------------------------------------------------------------------ MFU


class MfuAccountant:
    """Cumulative model-FLOPs utilization. `flops_per_step` comes from
    ops/flops.py's analytic matmul model (fwd+bwd, reuse-aware);
    `peak_flops` is the AGGREGATE peak over the learner's devices from
    the per-platform table (None — e.g. CPU smoke — suppresses
    compute_mfu; achieved FLOP/s is still reported so regressions stay
    visible even where utilization is meaningless)."""

    def __init__(self, flops_per_step: float, peak_flops: Optional[float]):
        self.flops_per_step = float(flops_per_step)
        self.peak_flops = peak_flops
        self._steps = 0
        self._seconds = 0.0

    def add_window(self, steps: int, seconds: float) -> None:
        self._steps += int(steps)
        self._seconds += max(float(seconds), 0.0)

    def scalars(self) -> Dict[str, float]:
        if self._seconds <= 0 or self._steps == 0:
            return {}
        achieved = self.flops_per_step * self._steps / self._seconds
        out = {"compute_flops_per_sec": achieved}
        if self.peak_flops:
            out["compute_mfu"] = achieved / self.peak_flops
        return out


# ------------------------------------------------------------- profiler


class CaptureBusyError(RuntimeError):
    """A jax.profiler capture is already in flight (jax supports one)."""


class ProfileCapture:
    """On-demand device/host trace windows. One capture at a time —
    jax.profiler owns process-global state — and each capture lands in
    its own TensorBoard-loadable dir under `out_dir`. The HTTP handler
    thread blocks inside capture() for the window; the learner loop is
    untouched (the profiler samples it from the side)."""

    def __init__(self, out_dir: str, max_seconds: float = 60.0):
        self.out_dir = out_dir or os.getcwd()
        self.max_seconds = max_seconds
        self._lock = threading.Lock()
        self.captures_done = 0
        self.last_path: Optional[str] = None

    def capture(self, seconds: float) -> Tuple[str, float]:
        """Trace for `seconds` (clamped to (0, max_seconds]) and return
        (trace dir, window actually traced) — one atomic result, so the
        HTTP handler echoes the clamped window of THIS capture, never a
        concurrent one's. Raises ValueError on a non-finite request and
        CaptureBusyError when a capture is in flight."""
        import jax
        import math

        seconds = float(seconds)
        if not math.isfinite(seconds):
            # NaN slides through min/max (both return nan) and would
            # reach time.sleep mid-trace — reject before touching the
            # profiler.
            raise ValueError(f"seconds must be finite, got {seconds!r}")
        seconds = min(max(seconds, 0.1), self.max_seconds)
        if not self._lock.acquire(blocking=False):
            raise CaptureBusyError("a profiler capture is already running")
        try:
            stamp = time.strftime("%Y%m%dT%H%M%S")
            path = os.path.join(self.out_dir, f"profile_{stamp}")
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            self.captures_done += 1
            self.last_path = path
            _log.info("profiler capture (%.1fs) written to %s", seconds, path)
            return path, seconds
        finally:
            self._lock.release()


# ----------------------------------------------------------- the bundle


class ComputeObserver:
    """One learner's compute-observability bundle: phase timer (optional:
    it fences the lane's puts), recompile sentinel, MFU accounting. Built by
    ObsRuntime.attach_compute(); everything funnels into window_scalars()
    on the learner's metrics cadence."""

    def __init__(
        self,
        flops_per_step: float,
        peak_flops: Optional[float],
        recorder=None,
        step_phases: bool = True,
    ):
        self.timer = StepPhaseTimer() if step_phases else None
        self.mfu = MfuAccountant(flops_per_step, peak_flops)
        self.sentinel: Optional[RecompileSentinel] = None
        self._recorder = recorder

    def wrap_train_step(self, fn, label: str = "train_step"):
        """Returns the sentinel-wrapped step; the learner swaps its
        train_step for this. Idempotent per ComputeObserver."""
        self.sentinel = RecompileSentinel(fn, label=label, recorder=self._recorder)
        return self.sentinel

    def window_scalars(self, steps: int, seconds: float) -> Dict[str, float]:
        """Everything compute_* for one metrics window: phase means (and
        reset), cumulative recompile/compile counters, cumulative
        MFU/FLOP-rate over windows seen so far."""
        self.mfu.add_window(steps, seconds)
        out: Dict[str, float] = {}
        if self.timer is not None:
            out.update(self.timer.window_scalars())
        if self.sentinel is not None:
            out.update(self.sentinel.scalars())
        out.update(self.mfu.scalars())
        return out
