"""dotaclient_tpu/obs/compute.py + obs/watchdog.py (ISSUE 3): step-phase
timing, recompile sentinel, MFU accounting, on-demand profiler capture,
and the acting watchdog.

Watchdog units run on an injected fake clock — no sleeps in tier-1.
Port-binding and profiler-capture tests carry `slow` per the marker
rules (tier-1 runs -m 'not slow'); the learner-window acceptance tests
stay in tier-1 (they are the PR's acceptance criteria).
"""

import json
import os
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import LearnerConfig, ObsConfig, PolicyConfig, WatchdogConfig
from dotaclient_tpu.obs.compute import (
    CaptureBusyError,
    MfuAccountant,
    ProfileCapture,
    RecompileSentinel,
    StepPhaseTimer,
    signature_diff,
    _described_leaves,
)
from dotaclient_tpu.obs.flight_recorder import FlightRecorder
from dotaclient_tpu.obs.http import MetricsHTTPServer
from dotaclient_tpu.obs.watchdog import Watchdog
from dotaclient_tpu.parallel.train_step import jit_cache_size
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout

from tests.test_transport import make_rollout

SMALL_POL = PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="float32")


# ------------------------------------------------------ step-phase timer


def test_step_phase_timer_window_means_and_reset():
    t = StepPhaseTimer()
    for _ in range(2):
        t.add("fetch", 0.3)
        t.add("pack", 0.05)
        t.add("h2d", 0.1)
        t.add("device_step", 0.4)
        t.add("host", 0.05)
        t.step(1.0)
    sc = t.window_scalars()
    assert sc["compute_phase_fetch_s"] == pytest.approx(0.3)
    assert sc["compute_phase_device_step_s"] == pytest.approx(0.4)
    assert sc["compute_phase_wall_s"] == pytest.approx(1.0)
    assert sc["compute_phase_fetch_frac"] == pytest.approx(0.3)
    # phases tile the wall (the acceptance property, exact at unit level)
    phase_sum = sum(sc[f"compute_phase_{p}_s"] for p in StepPhaseTimer.PHASES)
    assert phase_sum == pytest.approx(0.9)
    # the lane family rides every window; a lane that recorded nothing
    # reads zero busy and a full overlap, the loop's fetch as exposed wait
    assert sc["pipeline_prefetch_s"] == 0.0
    assert sc["pipeline_device_idle_s"] == pytest.approx(0.3)
    assert sc["pipeline_overlap_ratio"] == 1.0
    # window reset: an empty next window has zero means, no frac
    sc2 = t.window_scalars()
    assert sc2["compute_phase_fetch_s"] == 0.0
    assert "compute_phase_fetch_frac" not in sc2


# ----------------------------------------------------- recompile sentinel


def test_recompile_sentinel_two_shapes_exactly_one_recompile():
    """The satellite contract: steady-state shapes count ZERO recompiles;
    one deliberate shape change counts exactly ONE — and jit's own
    executable cache agrees with the sentinel's aval-hash count."""
    jitted = jax.jit(lambda x: x * 2.0)
    sentinel = RecompileSentinel(jitted, label="t")
    a = jnp.ones((4, 4))
    b = jnp.ones((8, 4))  # deliberate batch-shape change
    sentinel(a)
    sentinel(a)
    sentinel(a)
    assert sentinel.recompiles == 0 and sentinel.compiles == 1
    sentinel(b)
    assert sentinel.recompiles == 1 and sentinel.compiles == 2
    # both signatures cached now: NO further counting either way
    sentinel(a)
    sentinel(b)
    assert sentinel.recompiles == 1
    cache = jit_cache_size(jitted)
    if cache >= 0:  # jax exposes the probe on this version
        assert cache == sentinel.compiles
    assert sentinel.compile_s >= sentinel.last_compile_s > 0.0
    sc = sentinel.scalars()
    assert sc["compute_recompiles_total"] == 1.0
    assert sc["compute_compiles_total"] == 2.0


def test_recompile_sentinel_dumps_shape_diff_to_recorder(tmp_path):
    rec = FlightRecorder("learner", ring_size=16, dump_dir=str(tmp_path))
    sentinel = RecompileSentinel(jax.jit(lambda x: x + 1), label="ts", recorder=rec)
    sentinel(jnp.ones((4, 2)))
    sentinel(jnp.ones((6, 2)))
    events = list(rec._ring)
    assert [e["ev"] for e in events] == ["compile", "recompile"]
    diff = events[1]["diff"]
    assert any("(4, 2)" in d and "(6, 2)" in d for d in diff)
    assert events[1]["compile_s"] >= 0


def test_signature_diff_adds_removes_changes():
    old = _described_leaves({"a": np.zeros((2, 3)), "b": np.zeros(4, np.int32)})
    new = _described_leaves({"a": np.zeros((2, 5)), "c": np.zeros(1)})
    diffs = signature_diff(old, new)
    joined = " | ".join(diffs)
    assert "(2, 3)" in joined and "(2, 5)" in joined  # changed leaf
    assert any(d.startswith("+") for d in diffs)  # added c
    assert any(d.startswith("-") for d in diffs)  # removed b


# ------------------------------------------------------------------- MFU


def test_mfu_accountant_cumulative():
    acc = MfuAccountant(flops_per_step=100.0, peak_flops=1000.0)
    assert acc.scalars() == {}  # nothing seen yet
    acc.add_window(steps=5, seconds=1.0)
    acc.add_window(steps=5, seconds=1.0)
    sc = acc.scalars()
    assert sc["compute_flops_per_sec"] == pytest.approx(500.0)
    assert sc["compute_mfu"] == pytest.approx(0.5)


def test_mfu_accountant_no_peak_no_mfu():
    acc = MfuAccountant(flops_per_step=100.0, peak_flops=None)
    acc.add_window(4, 2.0)
    sc = acc.scalars()
    assert "compute_mfu" not in sc and sc["compute_flops_per_sec"] == pytest.approx(200.0)


def test_aggregate_peak_flops_table():
    from dotaclient_tpu.ops.flops import aggregate_peak_flops

    from types import SimpleNamespace as Dev

    v5e = Dev(platform="tpu", device_kind="TPU v5 lite")
    assert aggregate_peak_flops([v5e, v5e]) == pytest.approx(2 * 197e12)
    assert aggregate_peak_flops([Dev(platform="cpu", device_kind="cpu")]) is None
    assert aggregate_peak_flops([]) is None


# -------------------------------------------------------------- watchdog


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _wd(cfg, latest, version, recorder=None):
    clock = FakeClock()
    wd = Watchdog(cfg, latest_fn=latest, version_fn=version, recorder=recorder, time_fn=clock)
    return wd, clock


def test_watchdog_stall_escalation_ladder(tmp_path):
    """log (strike 1) → flight-recorder dump (strike 2) → trip (strike 3)
    → recovery clears the trip when the version advances again."""
    rec = FlightRecorder("learner", ring_size=32, dump_dir=str(tmp_path))
    version = [0]
    cfg = WatchdogConfig(enabled=True, stall_s=10.0, dump_after=2, trip_after=3)
    wd, clock = _wd(cfg, dict, lambda: version[0], recorder=rec)
    assert wd.check()["ok"]  # first check: baseline only, never a heartbeat
    version[0] = 1  # advance OBSERVED between checks: boot over, stall_s governs
    assert wd.check()["ok"]
    clock.t += 60  # version never advanced again
    v1 = wd.check()
    assert v1["strikes"] == 1 and v1["ok"] and "stall" in v1["reasons"][0]
    assert rec.last_dump_path is None
    v2 = wd.check()
    assert v2["strikes"] == 2 and v2["ok"]
    assert rec.last_dump_path is not None  # dump fired at dump_after
    v3 = wd.check()
    assert v3["strikes"] == 3 and not v3["ok"] and v3["tripped"]
    assert wd.scalars()["watchdog_ok"] == 0.0
    assert wd.trips_total == 1
    # recovery: version advances, next check clears strikes AND the trip
    version[0] = 5
    v4 = wd.check()
    assert v4["ok"] and not v4["tripped"] and v4["strikes"] == 0
    assert wd.scalars()["watchdog_ok"] == 1.0
    assert wd.trips_total == 1  # cumulative survives recovery


def test_watchdog_boot_grace_covers_slow_cold_start():
    """Before the FIRST version advance, stall uses max(stall_s,
    boot_grace_s): a slow compile/restore/first-batch wait must not
    crashloop the pod (the liveness restart would replay the same slow
    boot). After the grace expires with no step ever taken, stall DOES
    fire — a never-starting learner is still dead."""
    cfg = WatchdogConfig(enabled=True, stall_s=10.0, boot_grace_s=300.0, trip_after=1)
    wd, clock = _wd(cfg, dict, lambda: 0)
    clock.t += 120  # way past stall_s, inside the boot grace
    assert wd.check()["ok"]
    clock.t += 300  # grace exhausted, still no first step
    v = wd.check()
    assert not v["ok"] and "boot grace" in v["reasons"][0]


def test_watchdog_restore_version_write_does_not_end_boot_grace():
    """Checkpoint restore writes the version counter before the first
    train step. If the watchdog read that write as the first heartbeat,
    boot would end and the stall threshold would drop from boot_grace_s
    to stall_s while the restored learner is still in its minutes-long
    compile + first-batch wait — the liveness probe restarts the pod,
    the restart restores again: the exact crashloop boot_grace_s exists
    to prevent. The restore must land as the BASELINE; only an advance
    observed between checks (a real step) ends boot."""
    cfg = WatchdogConfig(enabled=True, stall_s=10.0, boot_grace_s=300.0, trip_after=1)
    version = [0]
    wd, clock = _wd(cfg, dict, lambda: version[0])
    version[0] = 4200  # restore lands before the watchdog's first look
    clock.t += 120  # well past stall_s, inside the boot grace
    assert wd.check()["ok"]  # restore write == baseline, not a heartbeat
    clock.t += 120  # 240s in, still no step: grace still governs
    assert wd.check()["ok"]
    version[0] = 4201  # the real first train step
    assert wd.check()["ok"]
    clock.t += 60  # booted now, so a 60s silence IS a stall (> stall_s)
    v = wd.check()
    assert not v["ok"] and "stall" in v["reasons"][0]


def test_watchdog_nan_loss_detected():
    cfg = WatchdogConfig(enabled=True, trip_after=1)
    wd, clock = _wd(cfg, lambda: {"loss": float("nan")}, lambda: 0)
    # advance version each check so stall never fires; nan still must
    versions = iter(range(1, 10))
    wd._version = lambda: next(versions)
    v = wd.check()
    assert not v["ok"] and "nan_loss" in v["reasons"][0]


def test_watchdog_starvation_from_fetch_frac():
    cfg = WatchdogConfig(enabled=True, starvation_frac=0.8, trip_after=1)
    latest = {"compute_phase_fetch_frac": 0.95, "loss": 0.1}
    versions = iter(range(1, 10))
    wd, clock = _wd(cfg, lambda: dict(latest), lambda: next(versions))
    v = wd.check()
    assert not v["ok"] and "starvation" in v["reasons"][0]
    latest["compute_phase_fetch_frac"] = 0.2
    assert wd.check()["ok"]


def test_watchdog_starvation_strikes_once_per_window():
    """Window detectors strike per failing WINDOW, not per check.
    latest() refreshes only every metrics_every steps while checks run
    every interval_s, so per-check judging would either trip on a
    transient episode that ended mid-window (3 re-reads of one stale
    sample in 15s restart a recovered learner) or — if stale samples
    were skipped — never accumulate the consecutive strikes sustained
    starvation deserves."""
    cfg = WatchdogConfig(enabled=True, starvation_frac=0.8, trip_after=3)
    latest = {"compute_phase_fetch_frac": 0.95, "loss": 0.1}
    state = {"v": 10, "seq": 10}  # seq: version at which latest() was logged
    clock = FakeClock()
    wd = Watchdog(
        cfg,
        latest_fn=lambda: dict(latest),
        version_fn=lambda: state["v"],
        time_fn=clock,
        latest_seq_fn=lambda: state["seq"],
    )
    v1 = wd.check()  # fresh failing window: strike 1 (log only)
    assert v1["ok"] and v1["strikes"] == 1 and "starvation" in v1["reasons"][0]
    for _ in range(6):  # same window re-read across many checks: count holds
        state["v"] += 1
        v = wd.check()
        assert v["ok"] and v["strikes"] == 1
    latest["compute_phase_fetch_frac"] = 0.2  # next window healthy: clears
    state["seq"] = state["v"]
    v = wd.check()
    assert v["ok"] and v["strikes"] == 0 and not v["reasons"]
    latest["compute_phase_fetch_frac"] = 0.95  # SUSTAINED: three failing
    for n in (1, 2, 3):  # consecutive windows walk the ladder to the trip
        state["v"] += 1
        state["seq"] = state["v"]
        v = wd.check()
        assert v["strikes"] == n
    assert not v["ok"] and v["tripped"]


def test_watchdog_reader_errors_hold_window_state():
    """A torn or unreadable (latest, seq) pair must neither consume a
    window's identity nor reset/re-judge its counts: the verdict holds
    and the next stable check judges the pending window."""
    cfg = WatchdogConfig(enabled=True, starvation_frac=0.8, trip_after=3)
    latest = {"compute_phase_fetch_frac": 0.95, "loss": 0.1}
    state = {"seq": 10, "seq_boom": False, "latest_boom": False}

    def seq_fn():
        if state["seq_boom"]:
            raise RuntimeError("metrics backend gone")
        return state["seq"]

    def latest_fn():
        if state["latest_boom"]:
            raise RuntimeError("metrics backend gone")
        return dict(latest)

    wd = Watchdog(cfg, latest_fn, lambda: 0, time_fn=FakeClock(), latest_seq_fn=seq_fn)
    assert wd.check()["strikes"] == 1  # window 10 judged once
    state["seq_boom"] = True
    v = wd.check()  # identity unreadable: held verdict, no re-judge
    assert v["ok"] and v["strikes"] == 1
    state["seq_boom"] = False
    state["latest_boom"] = True
    state["seq"] = 11  # a NEW window arrives but its data is unreadable
    v = wd.check()
    assert v["ok"] and v["strikes"] == 1  # identity NOT consumed, count held
    state["latest_boom"] = False
    v = wd.check()  # ...so the stable next check judges window 11 properly
    assert v["strikes"] == 2 and "2 consecutive windows" in v["reasons"][0]


def test_watchdog_regression_legacy_path_dedups_on_version():
    """Without a window identity wired (latest_seq_fn=None), baseline
    appends dedup on version advance — the pre-identity behavior — so a
    re-served sample between steps cannot flood the median with copies
    of itself."""
    cfg = WatchdogConfig(enabled=True, regression_frac=0.5, window=4, trip_after=1)
    latest = {"env_steps_per_sec": 100.0, "loss": 0.1}
    state = {"v": 1}
    wd = Watchdog(cfg, lambda: dict(latest), lambda: state["v"], time_fn=FakeClock())
    for _ in range(6):  # version parked across six checks: ONE sample
        assert wd.check()["ok"]
    assert len(wd._rates) == 1


def test_watchdog_regression_transient_dip_never_trips():
    """One dipped window (say a checkpoint write straddled the log) is
    ONE strike no matter how many checks re-read it before the next
    window, and a healthy next window clears it — the trailing baseline
    stays honest because the dip is appended exactly once."""
    cfg = WatchdogConfig(enabled=True, regression_frac=0.5, window=4, trip_after=3)
    latest = {"env_steps_per_sec": 100.0, "loss": 0.1}
    state = {"v": 0, "seq": 0}
    clock = FakeClock()
    wd = Watchdog(
        cfg,
        latest_fn=lambda: dict(latest),
        version_fn=lambda: state["v"],
        time_fn=clock,
        latest_seq_fn=lambda: state["seq"],
    )
    for s in range(1, 5):  # fill the baseline at the healthy rate
        state["seq"] = s
        state["v"] = s
        assert wd.check()["ok"]
    latest["env_steps_per_sec"] = 20.0  # one dipped window
    state["seq"] = 5
    for _ in range(6):  # many checks before the next window logs
        state["v"] += 1
        v = wd.check()
        assert v["ok"] and v["strikes"] == 1 and "regression" in v["reasons"][0]
    latest["env_steps_per_sec"] = 100.0  # recovered; next window clears
    state["seq"] = 12
    state["v"] = 12
    v = wd.check()
    assert v["ok"] and v["strikes"] == 0 and not v["reasons"]


def test_watchdog_regression_baseline_one_sample_per_window():
    """The trailing baseline holds one sample per metrics WINDOW. The
    train-step version advances every step while latest() re-serves the
    same logged sample, so keying the dedup on the version would append
    a duplicate each check and skew the median toward the newest
    window."""
    cfg = WatchdogConfig(enabled=True, regression_frac=0.5, window=4, trip_after=1)
    latest = {"env_steps_per_sec": 100.0, "loss": 0.1}
    state = {"v": 0, "seq": 1}

    def step_and_read():  # one train step per check; window unchanged
        state["v"] += 1
        return state["v"]

    wd = Watchdog(
        cfg,
        latest_fn=lambda: dict(latest),
        version_fn=step_and_read,
        time_fn=FakeClock(),
        latest_seq_fn=lambda: state["seq"],
    )
    for _ in range(6):
        assert wd.check()["ok"]
    assert len(wd._rates) == 1  # six checks, ONE window -> one sample
    for s in range(2, 6):  # four more windows at the healthy rate
        state["seq"] = s
        assert wd.check()["ok"]
    assert len(wd._rates) == 4
    latest["env_steps_per_sec"] = 30.0  # < 0.5 x median(100)
    state["seq"] = 6
    v = wd.check()
    assert not v["ok"] and "regression" in v["reasons"][0]


def test_watchdog_steps_regression_vs_trailing_median():
    cfg = WatchdogConfig(enabled=True, regression_frac=0.5, window=4, trip_after=1)
    latest = {"env_steps_per_sec": 100.0, "loss": 0.1}
    versions = iter(range(1, 50))
    wd, clock = _wd(cfg, lambda: dict(latest), lambda: next(versions))
    for _ in range(4):  # fill the trailing window at the healthy rate
        assert wd.check()["ok"]
    latest["env_steps_per_sec"] = 30.0  # < 0.5 x median(100)
    v = wd.check()
    assert not v["ok"] and "regression" in v["reasons"][0]


def test_watchdog_detector_error_is_healthy():
    """A latest_fn that throws must never crash or trip the watchdog."""
    cfg = WatchdogConfig(enabled=True, trip_after=1)

    def boom():
        raise RuntimeError("metrics backend gone")

    versions = iter(range(1, 10))
    wd, clock = _wd(cfg, boom, lambda: next(versions))
    assert wd.check()["ok"]


# ------------------------------------------------- healthz + /profile


@pytest.mark.slow  # binds a port + real HTTP roundtrips
def test_healthz_both_codes_and_body():
    """The satellite contract: structured JSON body, 200 healthy, 503
    once the provider reports not-ok, 200 again after recovery."""
    state = {"ok": True}

    def provider():
        return {
            "ok": state["ok"],
            "version": 7,
            "uptime_s": 12.5,
            "watchdog": {"enabled": True, "tripped": not state["ok"], "reasons": []},
        }

    server = MetricsHTTPServer(0, sources=[], health_provider=provider).start()
    try:
        url = f"http://127.0.0.1:{server.port}/healthz"
        body = json.loads(urllib.request.urlopen(url, timeout=10).read())
        assert body["ok"] is True and body["version"] == 7
        assert body["watchdog"]["enabled"] is True
        state["ok"] = False
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(url, timeout=10)
        assert exc.value.code == 503
        assert json.loads(exc.value.read())["watchdog"]["tripped"] is True
        state["ok"] = True
        assert json.loads(urllib.request.urlopen(url, timeout=10).read())["ok"] is True
    finally:
        server.stop()


@pytest.mark.slow  # binds a port + real HTTP roundtrips
def test_healthz_broken_provider_reads_unhealthy():
    def boom():
        raise RuntimeError("verdict source gone")

    server = MetricsHTTPServer(0, sources=[], health_provider=boom).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"http://127.0.0.1:{server.port}/healthz", timeout=10)
        assert exc.value.code == 503
    finally:
        server.stop()


@pytest.mark.slow  # binds a port + jax.profiler capture (filesystem + sleep)
def test_profile_endpoint_capture_and_errors(tmp_path):
    capture = ProfileCapture(str(tmp_path), max_seconds=0.4)
    # capture() returns (path, clamped-seconds) atomically; the handler
    # echoes the window actually traced
    server = MetricsHTTPServer(0, sources=[], profile_handler=capture.capture).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        # request far beyond max_seconds: clamped, and the response says so
        req = urllib.request.Request(f"{base}/profile?seconds=600", method="POST")
        body = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert os.path.isdir(body["trace_dir"])
        assert body["trace_dir"].startswith(str(tmp_path))
        assert body["seconds"] == pytest.approx(0.4)  # the CLAMPED window
        # jax wrote an actual TensorBoard-loadable trace into the dir
        found = [f for _, _, fs in os.walk(body["trace_dir"]) for f in fs]
        assert found, "profiler capture produced no trace files"
        # bad queries → 400, never a capture: non-numeric AND non-finite
        # (nan parses as a float and would poison the clamp)
        for bad in ("bogus", "nan", "inf"):
            req = urllib.request.Request(f"{base}/profile?seconds={bad}", method="POST")
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=10)
            assert exc.value.code == 400, bad
        assert capture.captures_done == 1  # no capture burned on bad input
        # no handler on GET routes: POST elsewhere is 404
        req = urllib.request.Request(f"{base}/metrics", method="POST")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req, timeout=10)
    finally:
        server.stop()


def test_profile_capture_rejects_non_finite(tmp_path):
    capture = ProfileCapture(str(tmp_path), max_seconds=5.0)
    with pytest.raises(ValueError, match="finite"):
        capture.capture(float("nan"))
    assert capture.captures_done == 0


def test_profile_capture_busy_guard(tmp_path):
    """Second concurrent capture must 409 (CaptureBusyError), not corrupt
    the in-flight one. Driven directly (no server, no real sleep race):
    hold the lock and call."""
    capture = ProfileCapture(str(tmp_path), max_seconds=5.0)
    assert capture._lock.acquire()
    try:
        with pytest.raises(CaptureBusyError):
            capture.capture(0.1)
    finally:
        capture._lock.release()


@pytest.mark.slow  # real jax.profiler capture: stop_trace serializes the
# process's accumulated trace state (observed ~13s mid-suite)
def test_profile_capture_clamps_to_max(tmp_path):
    capture = ProfileCapture(str(tmp_path), max_seconds=0.2)
    t0 = time.perf_counter()
    path, eff = capture.capture(60.0)  # clamped to 0.2s of tracing
    assert eff == pytest.approx(0.2)  # reports what it traced, not the ask
    # The clamp claim: nowhere near the requested 60s window. The bound
    # is loose because start/stop_trace overhead dominates the window.
    assert time.perf_counter() - t0 < 45.0
    assert os.path.isdir(path) and capture.captures_done == 1


# ---------------------------------------- learner acceptance (tier-1)


def _learner_cfg(name, tmp_path, **obs_kw):
    return LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=SMALL_POL,
        broker_url=f"mem://{name}",
        log_dir=str(tmp_path),
        metrics_every=1,
        # dump_dir pinned: a watchdog/crash dump from a test must land in
        # tmp, never the checkout cwd
        obs=ObsConfig(
            enabled=True, install_handlers=False, dump_dir=str(tmp_path), **obs_kw
        ),
    )


def _feed(broker, n, L=4, H=8):
    for i in range(n):
        broker.publish_experience(serialize_rollout(make_rollout(L=L, H=H, version=0, seed=i)))


def test_learner_step_phase_decomposition(tmp_path):
    """THE acceptance slice: one obs-enabled learner window logs the full
    compute_phase_* decomposition, the phases sum to ≈ the measured wall,
    and compute_recompiles_total stays 0 across steady-state steps."""
    from dotaclient_tpu.obs.compute import RecompileSentinel
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("compute_phases")
    broker = connect("mem://compute_phases")
    cfg = _learner_cfg("compute_phases", tmp_path)
    learner = Learner(cfg, connect("mem://compute_phases"))
    try:
        assert isinstance(learner.train_step, RecompileSentinel)  # sentinel armed
        _feed(broker, 32)
        steps = learner.run(num_steps=3, batch_timeout=60.0, max_idle=3)
    finally:
        learner.close()
    assert steps == 3
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    assert lines
    recs = [json.loads(l) for l in lines]
    # recompile sentinel: the FIRST window carries the one real compile;
    # every window holds recompiles at 0 (steady shapes)
    for r in recs:
        assert r["compute_recompiles_total"] == 0.0
    assert recs[-1]["compute_compiles_total"] == 1.0
    assert recs[0]["compute_compile_s"] > 0.0  # compile wall was measured
    # cumulative FLOP-rate accounting rode along (CPU: no compute_mfu)
    assert recs[-1]["compute_flops_per_sec"] > 0.0
    # phase decomposition: every phase present, and for windows after the
    # first (no compile wall inside the phases) the phase sum tiles the
    # iteration wall — ≥60% covered (loop bookkeeping is the remainder),
    # never exceeding it by more than timing noise
    last = recs[-1]
    phase_sum = 0.0
    for p in ("fetch", "pack", "h2d", "device_step", "host"):
        v = last[f"compute_phase_{p}_s"]
        assert v >= 0.0
        phase_sum += v
    wall = last["compute_phase_wall_s"]
    assert wall > 0.0
    assert phase_sum <= wall * 1.05 + 1e-4
    assert phase_sum >= wall * 0.6
    assert 0.0 <= last["compute_phase_fetch_frac"] <= 1.0


def test_learner_step_phases_off_keeps_loop_unfenced(tmp_path):
    """--obs.step_phases false: tracing/scrape stay, the loop keeps its
    pipelined shape (no timer), and no compute_phase_* scalars appear —
    but the sentinel/MFU families still do."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("compute_nophase")
    broker = connect("mem://compute_nophase")
    cfg = _learner_cfg("compute_nophase", tmp_path, step_phases=False)
    learner = Learner(cfg, connect("mem://compute_nophase"))
    try:
        assert learner.obs.compute.timer is None
        _feed(broker, 16)
        steps = learner.run(num_steps=2, batch_timeout=60.0, max_idle=3)
    finally:
        learner.close()
    assert steps == 2
    recs = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert all("compute_phase_wall_s" not in r for r in recs)
    assert recs[-1]["compute_recompiles_total"] == 0.0
    assert recs[-1]["compute_flops_per_sec"] > 0.0


@pytest.mark.nightly  # full subprocess learner + HTTP surface + profiler
@pytest.mark.slow  # nightly-heavy must ALSO be slow (tier-1 -m override)
def test_obs_smoke_script():
    """Nightly lane: scripts/obs_smoke.py curls /metrics + /healthz +
    POST /profile against a 20-step learner and reports one JSON line."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "obs_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"] is True and report["steps"] == 20
    assert not report["missing_required_scalars"]
    assert report["profile_trace_files"] > 0


@pytest.mark.slow  # binds a port; full learner loop + watchdog behind it
def test_learner_healthz_200_healthy_503_tripped(tmp_path):
    """Acceptance: a healthy watchdog-enabled learner serves 200 with the
    structured body; a tripped one serves 503; recovery restores 200."""
    import socket

    from dotaclient_tpu.runtime.learner import Learner

    sock = socket.socket()
    sock.bind(("", 0))
    port = sock.getsockname()[1]
    sock.close()

    mem.reset("wd_health")
    broker = connect("mem://wd_health")
    cfg = _learner_cfg("wd_health", tmp_path, metrics_port=port)
    # Thresholds no CI box can trip accidentally; check() is driven by
    # hand below, so the background cadence is irrelevant.
    cfg.obs.watchdog = WatchdogConfig(enabled=True, interval_s=3600.0, stall_s=1e9)
    learner = Learner(cfg, connect("mem://wd_health"))
    try:
        # Baseline check BEFORE training: boot ends only on a version
        # advance observed between checks (restore-safe contract), so
        # the post-run check below must have something to compare to.
        wd = learner.obs.watchdog
        assert wd.check()["ok"]
        _feed(broker, 16)
        assert learner.run(num_steps=2, batch_timeout=60.0, max_idle=3) == 2
        url = f"http://127.0.0.1:{port}/healthz"
        body = json.loads(urllib.request.urlopen(url, timeout=10).read())
        assert body["ok"] is True and body["role"] == "learner"
        assert body["version"] == 2 and body["uptime_s"] >= 0
        assert body["watchdog"]["enabled"] is True and body["watchdog"]["tripped"] is False
        # trip it: a genuinely-stalled version counter via the real ladder
        wd.cfg.stall_s = 0.0  # any non-advance now reads as stall
        # +1: the first check observes the run()'s version advance (ending
        # boot grace) and reads healthy; strikes start on the second
        for _ in range(wd.cfg.trip_after + 1):
            wd.check()
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(url, timeout=10)
        assert exc.value.code == 503
        tripped = json.loads(exc.value.read())
        assert tripped["ok"] is False and tripped["watchdog"]["tripped"] is True
        assert tripped["watchdog"]["reasons"]
        # watchdog_* gauges ride the scrape surface while tripped
        metrics = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "dotaclient_watchdog_ok 0" in metrics
        assert "dotaclient_watchdog_trips_total 1" in metrics
    finally:
        learner.close()
