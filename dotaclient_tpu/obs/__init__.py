"""Pipeline observability: tracing, flight recorder, scrape surface.

The ROADMAP north star is a production-scale deployment, but the
actor → broker → staging → replay → learner pipe had no per-hop timing
and no scrape endpoint — you could see THAT throughput was low
(env_steps_per_sec), never WHERE a rollout spent its time. This package
is the measurement layer:

- obs/spans.py        `span(name, **ids)`: the one primitive the learner
                      process times its threads' work with. Always on
                      (not behind --obs.*): a jax.profiler
                      TraceAnnotation on the device trace's clock while
                      a profiler session is open, cumulative span_*
                      scalars with every metrics window, the long ones
                      mirrored into the flight recorder where one
                      exists; also the compile counters and the OS
                      thread names;
- obs/trace.py        per-stage latency histograms from trace-stamped
                      rollout chunks (DTR2 wire extension) + the e2e
                      actor→apply scalar that decomposes staleness;
- obs/flight_recorder bounded ring of recent pipeline events, dumped to
                      JSON on crash / BatchLayoutError / SIGTERM;
- obs/http            stdlib-only Prometheus-text /metrics endpoint,
                      structured /healthz (503 when the watchdog trips),
                      POST /profile on-demand trace capture;
- obs/compute         learner compute decomposition: step-phase timer,
                      recompile sentinel, MFU accounting, ProfileCapture;
- obs/watchdog        liveness thread (stall/starvation/NaN/regression →
                      log → dump → 503) behind --obs.watchdog.*;
- obs/registry        the documented scalar-name contract + drift guard.

Everything else is opt-in via --obs.* and default-off with zero hot-path
overhead: no tracer/recorder objects exist, wire frames stay
byte-identical DTR1, staging/learner take their pre-obs paths
unchanged (asserted in tests/test_obs.py).

`ObsRuntime` is the per-process bundle the binaries construct:

    self.obs = ObsRuntime.create(cfg.obs, role="learner")  # or None

Actors use stamp() to trace outgoing chunks; the learner hands
`tracer`/`recorder` to its StagingBuffer and starts the scrape server
with live gauge sources.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

from dotaclient_tpu.config import ObsConfig
from dotaclient_tpu.obs.compute import ComputeObserver, ProfileCapture
from dotaclient_tpu.obs.flight_recorder import FlightRecorder
from dotaclient_tpu.obs.http import MetricsHTTPServer
from dotaclient_tpu.obs.trace import LATENCY_EDGES_MS, STAGES, PipelineTracer, TraceRef
from dotaclient_tpu.obs.watchdog import Watchdog

__all__ = [
    "LATENCY_EDGES_MS",
    "STAGES",
    "ComputeObserver",
    "FlightRecorder",
    "MetricsHTTPServer",
    "ObsRuntime",
    "PipelineTracer",
    "ProfileCapture",
    "TraceRef",
    "Watchdog",
]


class ObsRuntime:
    """One process's observability bundle: recorder + tracer (+ scrape
    server for processes that call serve_metrics)."""

    def __init__(self, cfg: ObsConfig, role: str):
        self.cfg = cfg
        self.role = role
        self.recorder = FlightRecorder(
            role, ring_size=cfg.ring_size, dump_dir=cfg.dump_dir
        )
        self.tracer = PipelineTracer(recorder=self.recorder)
        self.server: Optional[MetricsHTTPServer] = None
        self.compute: Optional[ComputeObserver] = None
        self.watchdog: Optional[Watchdog] = None
        self.profiler: Optional[ProfileCapture] = None
        self._trace_seq = 0

    @classmethod
    def create(cls, cfg: ObsConfig, role: str) -> Optional["ObsRuntime"]:
        """None when obs is disabled — callers keep a single `if self.obs
        is None` guard and the disabled path constructs nothing."""
        if not cfg.enabled:
            return None
        rt = cls(cfg, role)
        if cfg.install_handlers:
            rt.recorder.install_handlers()
        return rt

    # ------------------------------------------------------------- actor

    def stamp(self, rollout, actor_id: int):
        """Trace-stamp an outgoing rollout chunk (actor publish path):
        allocates the trace id, stamps birth, records the publish event.
        Returns the stamped Rollout (serialize_rollout then emits DTR2)."""
        self._trace_seq += 1
        # High word = actor, low word = per-process sequence: ids stay
        # unique across the fleet without coordination, and a dump's
        # trace id alone names the publishing actor.
        trace_id = ((actor_id & 0xFFFFFFFF) << 32) | (self._trace_seq & 0xFFFFFFFF)
        birth = time.time()
        self.recorder.record("publish", t=birth, trace=trace_id, actor=actor_id)
        return rollout._replace(trace_id=trace_id, birth_time=birth)

    # ----------------------------------------------------------- compute

    def attach_compute(
        self, flops_per_step: float, peak_flops: Optional[float]
    ) -> ComputeObserver:
        """Build the learner's compute bundle (obs/compute.py): phase
        timer (when cfg.step_phases), recompile sentinel factory, MFU
        accounting — all sharing this runtime's flight recorder."""
        self.compute = ComputeObserver(
            flops_per_step,
            peak_flops,
            recorder=self.recorder,
            step_phases=self.cfg.step_phases,
        )
        return self.compute

    def attach_watchdog(
        self, latest_fn, version_fn, latest_seq_fn=None
    ) -> Optional[Watchdog]:
        """Build + start the liveness watchdog when cfg.watchdog.enabled;
        its verdict feeds the /healthz provider and its scalars the
        scrape surface. Call AFTER checkpoint restore: the watchdog
        treats version advances as train-step heartbeats, and boot grace
        must outlive the restore's version write. latest_seq_fn
        (MetricsLogger.latest_step) identifies the metrics window behind
        latest_fn so per-check detectors can tell a fresh sample from a
        re-read of one already judged."""
        if not self.cfg.watchdog.enabled:
            return None
        self.watchdog = Watchdog(
            self.cfg.watchdog,
            latest_fn,
            version_fn,
            recorder=self.recorder,
            latest_seq_fn=latest_seq_fn,
        ).start()
        return self.watchdog

    # ------------------------------------------------------------ scrape

    def serve_metrics(
        self,
        sources: List[Callable[[], Dict[str, float]]],
        health_provider: Optional[Callable[[], Dict]] = None,
    ) -> Optional[MetricsHTTPServer]:
        """Start the /metrics endpoint when cfg.metrics_port is set (> 0).
        Adds the tracer's scalars as an implicit source, the watchdog's
        gauges when one is attached, and wires /healthz + POST /profile
        (a ProfileCapture is built lazily here — the capture dir falls
        back dump_dir → cwd)."""
        if self.cfg.metrics_port <= 0:
            return None
        sources = list(sources) + [self.tracer.scalars]

        # Late-bound: a watchdog attached AFTER the server starts (no
        # ordering contract on callers) still appears on the scrape. The
        # local rebind inside the closure makes the None-check and the
        # call one atomic observation — close() nulls self.watchdog from
        # another thread while scrape handlers run this.
        def _watchdog_scalars() -> Dict[str, float]:
            wd = self.watchdog
            return wd.scalars() if wd is not None else {}

        sources.append(_watchdog_scalars)
        if self.profiler is None:
            self.profiler = ProfileCapture(
                self.cfg.profile_dir or self.cfg.dump_dir,
                max_seconds=self.cfg.profile_max_seconds,
            )
        self.server = MetricsHTTPServer(
            self.cfg.metrics_port,
            sources,
            health_provider=health_provider,
            # capture() returns (path, clamped-window) atomically — the
            # obs/http.py handler echoes the window actually traced
            profile_handler=self.profiler.capture,
            # GET /debug/flight: every ObsRuntime-served binary exposes
            # its crash ring for fleetd's incident fan-in.
            flight_provider=self.recorder.snapshot,
        ).start()
        return self.server

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
            self.watchdog = None
        if self.server is not None:
            self.server.stop()
            self.server = None
