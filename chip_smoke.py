#!/usr/bin/env python3
"""Start the system on the chip once, through the entry points a user
calls, at the full width of the flagship policy (PolicyConfig defaults:
128/128/128 bf16; the learner's default batch 256 x seq 16).

    python chip_smoke.py            # one chip: learner phase, then serve phase
    python chip_smoke.py --chips 4  # four chips: ONLY the dp=4 step vs one device

Default run, one chip, two phases one after the other (a chip serves one
process at a time; this parent never initialises a JAX backend, it starts
the binaries, reads what they log and stops every one of them):

- learner: fake dotaservice + TCP broker + actor binaries pinned to the
  CPU + `python -m dotaclient_tpu.runtime.learner --platform tpu
  --train_steps K`. Passes when the learner exits 0 inside the timeout,
  says it ran on the expected platform with the Pallas LSTM kernel in
  its step, took K optimizer steps on batches that came over the wire,
  every loss is finite, and rollouts stamped with a published weight
  version are on the wire (the D2H publish leg reached the actors).
- serve: `python -m dotaclient_tpu.serve.server --platform tpu` + an
  actor binary stepping against it with --serve.endpoint. Passes when
  the ready line is read, the server says it runs on the expected
  platform, a few hundred steps were answered and a carry is resident
  for every env. No learner publishes in this phase (it cannot share the
  chip), so no weight hot-swap is run.

--chips 4 runs, in this one process, the learner's flagship train step
over mesh "dp=-1" on four devices and the same batches and seed on a
one-device mesh: losses must agree, batch shards must sit on four
distinct devices with the parameters replicated on all four, and the
kernel must be in both programs.

Each phase prints one JSON object; times in them are what this smoke
happened to see, not benchmark numbers. The last line is
{"ok": true, "device": {"platform", "kind", "count"}} with the device as
the process that ran the step reported it. Any failure exits non-zero
with {"ok": false, ...} last; without a TPU that takes seconds.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import re
import shutil
import socket
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

LEARNER_STEPS = 8  # one compile, seven warm steps
LEARNER_TIMEOUT_S = 420.0
ACTOR_PROCS = 2
ENVS_PER_ACTOR = 16
SERVE_ENVS = 8  # <= serve.max_batch (16): one tick can hold every env
SERVE_STEPS = 300
SERVE_TIMEOUT_S = 180.0  # for the boot and again for the served steps
MULTICHIP_STEPS = 4
# |loss(dp=4) - loss(1 device)| <= LOSS_ATOL + LOSS_RTOL * |loss|: same
# batches, seed and bf16 math; only the order of the cross-device
# gradient and loss reductions differs. Seen on four v5e chips: 7.6e-6
# at losses of 0.14-0.17; the bound is ~30 times that.
LOSS_RTOL = 1e-3
LOSS_ATOL = 1e-4


class SmokeFailure(Exception):
    """A phase did not meet its pass conditions."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _versions() -> dict:
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    return out


class Procs:
    """The child processes of one phase. Each runs `python -m <module>`
    from `workdir` with stdout and stderr in <workdir>/<name>.log; every
    one still alive is stopped when the block ends, however it ends —
    after the ends of their logs went to stderr, if a phase failed."""

    def __init__(self, workdir: str):
        self.workdir = workdir
        self._procs: dict = {}
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(p for p in (REPO, env.get("PYTHONPATH")) if p)
        self._env = env

    def __enter__(self) -> "Procs":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is SmokeFailure:
            print(self.tails(), file=sys.stderr, flush=True)
        for p in self._procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self._procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def start(self, name: str, module: str, *flags) -> subprocess.Popen:
        with open(os.path.join(self.workdir, f"{name}.log"), "wb") as log:
            p = subprocess.Popen(
                [sys.executable, "-m", module, *map(str, flags)],
                cwd=self.workdir,
                env=self._env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )
        self._procs[name] = p
        return p

    def log(self, name: str) -> str:
        with open(os.path.join(self.workdir, f"{name}.log"), errors="replace") as f:
            return f.read()

    def find(self, name: str, pattern: str):
        """First match of `pattern` in <name>'s log, or a failure that
        says what the process never stated."""
        m = re.search(pattern, self.log(name))
        if m is None:
            raise SmokeFailure(f"{name} never logged /{pattern}/")
        return m

    def wait_until(self, what, ready, timeout_s: float, may_exit=()) -> None:
        """Poll `ready()` until true. Any child exiting meanwhile, bar
        those named in `may_exit`, fails the phase at once. `what` (a
        string, or a callable giving one) names the wait in a failure."""
        deadline = time.monotonic() + timeout_s
        while not ready():
            what_now = what() if callable(what) else what
            for name, p in self._procs.items():
                if name not in may_exit and p.poll() is not None:
                    raise SmokeFailure(
                        f"{name} exited with code {p.returncode} while waiting for {what_now}"
                    )
            if time.monotonic() > deadline:
                raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for {what_now}")
            time.sleep(0.25)

    def tails(self, n: int = 30) -> str:
        out = []
        for name in self._procs:
            # XLA:CPU logs two ~3 KB lines per cache entry it loads
            lines = [l for l in self.log(name).splitlines() if "cpu_aot_loader" not in l]
            out.append(f"--- {name} (last {n} lines) ---\n" + "\n".join(l[:400] for l in lines[-n:]))
        return "\n".join(out)


def _start_env_and_broker(procs: Procs):
    env_port, broker_port = _free_port(), _free_port()
    procs.start("env", "dotaclient_tpu.env.fake_dotaservice", "--port", env_port)
    procs.start("broker", "dotaclient_tpu.transport.tcp_server", "--port", broker_port)
    procs.wait_until(
        "env and broker to listen",
        lambda: "listening" in procs.log("env") and "listening" in procs.log("broker"),
        60.0,
    )
    return f"127.0.0.1:{env_port}", f"tcp://127.0.0.1:{broker_port}"


def _device_facts(procs: Procs, name: str, role: str, platform: str) -> dict:
    m = procs.find(name, rf"{role} up: platform=(\S+) device_kind=(.+?) devices=(\d+)")
    device = {"platform": m.group(1), "kind": m.group(2), "count": int(m.group(3))}
    if device["platform"] != platform:
        raise SmokeFailure(f"{name} ran on {device['platform']!r}, not {platform!r}")
    return device


def _cache_facts(procs: Procs, name: str) -> dict:
    m = procs.find(name, r"compile_cache=(\S+) hits=(\d+) misses=(\d+)")
    return {"dir": m.group(1), "hits": int(m.group(2)), "misses": int(m.group(3))}


def learner_phase(
    workdir: str,
    platform: str = "tpu",
    lstm_impl: str = "pallas",
    steps: int = LEARNER_STEPS,
    policy_flags=(),
    learner_flags=(),
    timeout_s: float = LEARNER_TIMEOUT_S,
) -> dict:
    """Fleet + learner binary for `steps` optimizer steps. `platform` and
    `lstm_impl` are what the learner must SAY it ran; the flag tuples let
    a test shrink the policy and batch (main() passes none)."""
    from dotaclient_tpu.transport.base import connect
    from dotaclient_tpu.transport.serialize import deserialize_rollout

    with Procs(workdir) as procs:
        env_addr, broker_url = _start_env_and_broker(procs)
        t0 = time.monotonic()
        learner = procs.start(
            "learner",
            "dotaclient_tpu.runtime.learner",
            *("--platform", platform, "--broker_url", broker_url),
            *("--train_steps", steps, "--metrics_every", 1),
            *("--obs.enabled", "true", "--obs.install_handlers", "false"),
            *policy_flags,
            *learner_flags,
        )
        for i in range(ACTOR_PROCS):
            procs.start(
                f"actor{i}",
                "dotaclient_tpu.runtime.actor",
                *("--platform", "cpu", "--env_addr", env_addr, "--broker_url", broker_url),
                *("--rollout_len", 16, "--envs_per_process", ENVS_PER_ACTOR),
                *("--actor_id", i, "--seed", i),
                *policy_flags,
            )
        procs.wait_until(
            f"the learner to take {steps} steps",
            lambda: learner.poll() is not None,
            timeout_s,
            may_exit=("learner",),
        )
        wall_s = time.monotonic() - t0
        if learner.returncode != 0:
            raise SmokeFailure(f"learner exited with code {learner.returncode}")

        device = _device_facts(procs, "learner", "learner", platform)
        impl = procs.find("learner", r"lstm recurrence: impl=(\S+) \(asked (\S+)\)")
        if impl.group(1) != lstm_impl:
            raise SmokeFailure(
                f"the learner's step runs lstm impl {impl.group(1)!r}, not {lstm_impl!r}"
            )
        ready = procs.find("learner", r"learner ready: mesh=(\{.*?\}) batch=(\d+)x(\d+) packer=(\S+)")
        done = procs.find(
            "learner",
            r"learner done: version=(\d+) env_steps=(\d+) wire_frames=(\d+) weights_published=(\d+)",
        )
        taken, env_steps, wire_frames, published = map(int, done.groups())
        compile_s = float(procs.find("learner", r"train_step compiled in ([\d.]+)s").group(1))
        # --metrics_every 1: the learner logs every step's window
        windows = [
            (int(m.group(1)), float(m.group(2)), float(m.group(3)))
            for m in re.finditer(
                r"step (\d+): loss=(\S+) env_steps_per_sec=\S+ time_step_s=(\S+)",
                procs.log("learner"),
            )
        ]
        if taken != steps or [w[0] for w in windows] != list(range(1, steps + 1)):
            raise SmokeFailure(
                f"learner took {taken} steps and logged {[w[0] for w in windows]}, wanted {steps}"
            )
        losses = [w[1] for w in windows]
        if not all(math.isfinite(x) for x in losses):
            raise SmokeFailure(f"non-finite loss in {losses}")
        batch = int(ready.group(2))
        if wire_frames < steps * batch:
            raise SmokeFailure(
                f"{wire_frames} rollout frames came over the wire, "
                f"{steps} batches of {batch} need {steps * batch}"
            )
        if published < 1:
            raise SmokeFailure("the learner published no weights")
        # The publish leg, observed where it ends: the actors are still
        # running, and a rollout they put on the wire after applying a
        # published version is stamped with it. Read past the backlog
        # of version-0 rollouts from before the first publish.
        broker = connect(broker_url)
        actor_version = frames_read = 0
        deadline = time.monotonic() + 60.0
        while actor_version < 1 and time.monotonic() < deadline:
            frames = broker.consume_experience(512, timeout=5.0)
            frames_read += len(frames)
            actor_version = max((deserialize_rollout(f).version for f in frames), default=0)
        if actor_version < 1:
            raise SmokeFailure(
                f"no rollout on the wire carries a published weight version "
                f"({frames_read} frames read in 60 s)"
            )
        return {
            "phase": "learner",
            "device": device,
            "mesh": ready.group(1),
            "batch": f"{batch}x{ready.group(3)}",
            "lstm_impl": impl.group(1),
            "lstm_impl_asked": impl.group(2),
            "packer": ready.group(4),
            "steps": steps,
            "env_steps_trained": env_steps,
            "wire_frames_consumed": wire_frames,
            "losses": losses,
            "compile_s": compile_s,
            "warm_step_s_median": statistics.median(w[2] for w in windows[1:]),
            "weights_published": published,
            "actor_weight_version_seen": actor_version,
            "compile_cache": _cache_facts(procs, "learner"),
            "wall_s": round(wall_s, 1),
            "versions": _versions(),
        }


def serve_phase(
    workdir: str,
    platform: str = "tpu",
    steps: int = SERVE_STEPS,
    policy_flags=(),
    timeout_s: float = SERVE_TIMEOUT_S,
) -> dict:
    """Serve binary on `platform` + an actor binary stepping against it."""
    from dotaclient_tpu.control.scrape import scrape_endpoint

    with Procs(workdir) as procs:
        env_addr, broker_url = _start_env_and_broker(procs)
        metrics_port = _free_port()
        t0 = time.monotonic()
        procs.start(
            "server",
            "dotaclient_tpu.serve.server",
            *("--platform", platform, "--serve.port", 0, "--broker_url", broker_url),
            *("--obs.enabled", "true", "--obs.metrics_port", metrics_port),
            *("--obs.install_handlers", "false"),
            *policy_flags,
        )
        ready_line = r'\{"serving": true, "port": (\d+)\}'
        procs.wait_until(
            "the server's ready line",
            lambda: re.search(ready_line, procs.log("server")) is not None,
            timeout_s,
        )
        boot_s = time.monotonic() - t0
        serve_port = int(procs.find("server", ready_line).group(1))
        procs.start(
            "actor",
            "dotaclient_tpu.runtime.actor",
            *("--platform", "cpu", "--env_addr", env_addr, "--broker_url", broker_url),
            *("--serve.endpoint", f"127.0.0.1:{serve_port}"),
            *("--rollout_len", 16, "--envs_per_process", SERVE_ENVS),
            *policy_flags,
        )
        seen: dict = {}

        def served() -> bool:
            seen.update(scrape_endpoint(f"127.0.0.1:{metrics_port}") or {})
            return (
                seen.get("serve_requests_total", 0) >= steps
                and seen.get("serve_carries_resident", 0) == SERVE_ENVS
            )

        t1 = time.monotonic()
        procs.wait_until(
            lambda: f"{steps} served steps with {SERVE_ENVS} carries resident (last scrape: "
            f"{seen.get('serve_requests_total')} steps, "
            f"{seen.get('serve_carries_resident')} carries)",
            served,
            timeout_s,
        )
        return {
            "phase": "serve",
            "device": _device_facts(procs, "server", "serve", platform),
            "served_steps": int(seen["serve_requests_total"]),
            "carries_resident": int(seen["serve_carries_resident"]),
            "envs": SERVE_ENVS,
            "bad_requests": int(seen.get("serve_bad_requests_total", 0)),
            "hot_swap": "not run: no learner publishes in this phase",
            "boot_and_compile_s": round(boot_s, 1),
            "serving_s": round(time.monotonic() - t1, 1),
            "compile_cache": _cache_facts(procs, "server"),
        }


def multichip_phase(
    n_devices: int = 4,
    platform: str = "tpu",
    lstm_impl: str = "pallas",
    cfg=None,
    steps: int = MULTICHIP_STEPS,
) -> dict:
    """The learner's train step over mesh "dp=-1" on `n_devices` against
    the same batches and seed on a one-device mesh, in THIS process.
    `cfg` lets a test shrink the policy (main() passes none: the
    flagship LearnerConfig)."""
    import jax
    import numpy as np

    from dotaclient_tpu.config import LearnerConfig
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import (
        build_single_train_step,
        init_train_state,
        make_train_batch,
    )
    from dotaclient_tpu.runtime.device import use_compile_cache
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    cache = use_compile_cache()
    devices = jax.devices()
    first = devices[0]
    if first.platform != platform or len(devices) < n_devices:
        raise SmokeFailure(
            f"needs {n_devices} {platform} devices, found {len(devices)} {first.platform}"
        )
    devices = devices[:n_devices]
    cfg = cfg if cfg is not None else LearnerConfig()
    host_batches = [
        cast_obs_to_compute_dtype(cfg, jax.tree.map(np.asarray, make_train_batch(cfg, seed)))
        for seed in range(steps)
    ]

    def run(mesh_devices) -> dict:
        mesh = mesh_lib.make_mesh("dp=-1", devices=mesh_devices)
        step, state_shardings, io = build_single_train_step(cfg, mesh)
        state = jax.device_put(
            init_train_state(cfg, jax.random.PRNGKey(cfg.seed)), state_shardings
        )
        payloads = [
            jax.device_put(io.pack_transfer(b), io.sharding) for b in host_batches
        ]
        t0 = time.perf_counter()
        compiled = step.lower(state, payloads[0]).compile()
        compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        batch_devices = {s.device for s in payloads[0].addressable_shards}
        shard_rows = {s.data.shape[0] for s in payloads[0].addressable_shards}
        params_replicated = all(
            leaf.sharding.is_fully_replicated and leaf.sharding.device_set == set(mesh_devices)
            for leaf in jax.tree.leaves(state.params)
        )
        losses, step_s = [], []
        for payload in payloads:
            t0 = time.perf_counter()
            state, metrics = compiled(state, payload)
            losses.append(float(metrics["loss"]))  # waits for the device
            step_s.append(time.perf_counter() - t0)
        return {
            "devices": len(mesh_devices),
            "batch_shard_devices": len(batch_devices),
            "batch_shard_rows": sorted(shard_rows),
            "params_replicated_on_all": params_replicated,
            "kernel_in_program": "tpu_custom_call" in text,
            "all_reduces_in_program": len(re.findall(r"= \S+ all-reduce(?:-start)?\(", text)),
            "losses": losses,
            "compile_s": round(compile_s, 2),
            "warm_step_s_median": statistics.median(step_s[1:]),
        }

    many, one = run(devices), run(devices[:1])
    result = {
        "phase": f"chips{n_devices}",
        "device": {"platform": first.platform, "kind": first.device_kind, "count": len(jax.devices())},
        "batch": f"{cfg.batch_size}x{cfg.seq_len}",
        "dp": many,
        "one_device": one,
        "loss_tolerance": {"rtol": LOSS_RTOL, "atol": LOSS_ATOL},
        "max_abs_loss_diff": max(abs(a - b) for a, b in zip(many["losses"], one["losses"])),
        "compile_cache": {"dir": cache.dir, "hits": cache.hits, "misses": cache.misses},
        "versions": _versions(),
    }
    emit(result)  # before the checks: a comparison that fails still shows its numbers
    if many["batch_shard_devices"] != n_devices or many["batch_shard_rows"] != [
        cfg.batch_size // n_devices
    ]:
        raise SmokeFailure(
            f"batch shards sit on {many['batch_shard_devices']} devices with rows "
            f"{many['batch_shard_rows']}, wanted {n_devices} x {cfg.batch_size // n_devices}"
        )
    if not many["params_replicated_on_all"]:
        raise SmokeFailure(f"parameters are not replicated on all {n_devices} devices")
    want_kernel = lstm_impl == "pallas"
    if many["kernel_in_program"] != want_kernel or one["kernel_in_program"] != want_kernel:
        raise SmokeFailure(
            f"tpu_custom_call in the dp program: {many['kernel_in_program']}, in the "
            f"one-device program: {one['kernel_in_program']}; wanted {want_kernel}"
        )
    if n_devices > 1 and not many["all_reduces_in_program"]:
        raise SmokeFailure("the dp program holds no all-reduce: gradients are not reduced")
    for a, b in zip(many["losses"], one["losses"]):
        if not (math.isfinite(a) and math.isfinite(b)) or abs(a - b) > LOSS_ATOL + LOSS_RTOL * abs(b):
            raise SmokeFailure(
                f"losses disagree: dp {many['losses']} vs one device {one['losses']}"
            )
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        default=1,
        choices=(1, 4),
        help="1 (default): learner phase then serve phase on one chip; "
        "4: only the dp=4 train step against a one-device mesh",
    )
    args = ap.parse_args(argv)
    try:
        if args.chips == 4:
            device = multichip_phase(4)["device"]
        else:
            workdir = tempfile.mkdtemp(prefix="chip_smoke_")
            try:
                for phase in (learner_phase, serve_phase):
                    sub = os.path.join(workdir, phase.__name__)
                    os.mkdir(sub)
                    result = phase(sub)
                    emit(result)
                    if phase is learner_phase:
                        device = result["device"]
            finally:
                shutil.rmtree(workdir, ignore_errors=True)
    except SmokeFailure as e:
        emit({"ok": False, "error": str(e)})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
