"""Multi-host learner path (SURVEY.md §5 "Distributed communication
backend"): `--multihost` runs jax.distributed.initialize() before
backend init, then the ordinary mesh/SPMD step.

A true N-host cluster needs N machines; what IS provable here is the
whole code path end-to-end at num_processes=1 — distributed runtime up,
coordinator handshake, device mesh over the virtual 8-CPU topology, real
frames through the staging buffer, two full train steps, clean exit.
Run in a SUBPROCESS because jax.distributed.initialize is irreversible
in-process and would poison other tests' backends.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Some CPU-only jax builds refuse cross-process collectives outright;
# that is an environment limitation, not a repo regression — the
# 2-process tests skip on it instead of failing the gate.
_CPU_MULTIPROCESS_UNSUPPORTED = "Multiprocess computations aren't implemented"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_processes(script_fn, timeout: float = 420):
    """Spawn process_id 0 and 1, join both, and return [(rc, out, err)].
    Skips the caller when the environment's jax cannot run multiprocess
    collectives on the CPU backend (same guard for every 2-process test)."""
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script_fn(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            cwd=REPO_ROOT,
        )
        for pid in (0, 1)
    ]
    outs = []
    for pr in procs:
        try:
            out, err = pr.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for p2 in procs:
                p2.kill()
            raise
        outs.append((pr.returncode, out, err))
    if any(rc != 0 and _CPU_MULTIPROCESS_UNSUPPORTED in err for rc, _, err in outs):
        pytest.skip(f"jax build: {_CPU_MULTIPROCESS_UNSUPPORTED} on the CPU backend")
    return outs


def test_multihost_single_process_trains():
    port = _free_port()
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        from dotaclient_tpu.config import LearnerConfig, PolicyConfig
        from dotaclient_tpu.transport.base import connect
        from dotaclient_tpu.transport.serialize import serialize_rollout
        from tests.test_transport import make_rollout
        import dotaclient_tpu.runtime.learner as learner_mod

        # pre-load the in-process broker the learner main will connect to
        broker = connect("mem://mh")
        for i in range(24):
            broker.publish_experience(serialize_rollout(make_rollout(L=4, H=16, version=0, seed=i)))

        learner_mod.main([
            "--multihost", "true",
            "--coordinator", "127.0.0.1:{port}",
            "--num_processes", "1",
            "--process_id", "0",
            "--platform", "cpu",
            "--broker_url", "mem://mh",
            "--batch_size", "8",
            "--seq_len", "4",
            "--train_steps", "2",
            "--mesh_shape", "dp=-1",
            "--policy.unit_embed_dim", "16",
            "--policy.lstm_hidden", "16",
            "--policy.mlp_hidden", "16",
            "--policy.dtype", "float32",
        ])
        import jax
        assert jax.process_count() == 1, jax.process_count()
        assert len(jax.devices()) == 8, jax.devices()
        print("MULTIHOST_OK devices=", len(jax.devices()))
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        timeout=300,
        text=True,
        cwd=REPO_ROOT,  # the script imports `tests.*` / `dotaclient_tpu`
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIHOST_OK" in out.stdout, (out.stdout, out.stderr[-2000:])


def test_multihost_two_processes_train_together():
    """TWO actual OS processes form one jax.distributed cluster (CPU
    backend, 4 virtual devices each -> 8 global) and run the SAME SPMD
    train step over a mesh spanning both — the DCN story exercised for
    real, not at num_processes=1: coordinator handshake, cross-process
    device visibility, per-process staging of the LOCAL batch share
    into ONE [B_local, row_bytes] u8 buffer,
    make_array_from_process_local_data assembly, in-jit unpack, compiler collectives
    across the process boundary, and process-0-gated weight publishing.

    Topology note: the per-process mem:// brokers here stand in for the
    SHARED cluster broker production uses (mem cannot span processes).
    That is fine for a 2-step run — every frame is stamped v0, within
    max_staleness — but a LONG run on private brokers would starve
    non-primary hosts' actors of weights (only process 0 publishes) and
    eventually stall staging; the learner logs a warning for exactly
    this combination. Production: one tcp://-or-amqp:// broker shared by
    all hosts.
    """
    port = _free_port()

    def script(pid: int) -> str:
        return textwrap.dedent(
            f"""
            import os
            os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
            from dotaclient_tpu.config import LearnerConfig, PolicyConfig
            from dotaclient_tpu.transport.base import connect
            from dotaclient_tpu.transport.serialize import serialize_rollout
            from tests.test_transport import make_rollout
            import dotaclient_tpu.runtime.learner as learner_mod

            broker = connect("mem://mh2_{pid}")
            for i in range(32):
                broker.publish_experience(serialize_rollout(make_rollout(L=4, H=16, version=0, seed=100*{pid}+i)))

            learner_mod.main([
                "--multihost", "true",
                "--coordinator", "127.0.0.1:{port}",
                "--num_processes", "2",
                "--process_id", "{pid}",
                "--platform", "cpu",
                "--broker_url", "mem://mh2_{pid}",
                "--batch_size", "8",
                "--seq_len", "4",
                "--train_steps", "2",
                "--mesh_shape", "dp=-1",
                "--policy.unit_embed_dim", "16",
                "--policy.lstm_hidden", "16",
                "--policy.mlp_hidden", "16",
                "--policy.dtype", "float32",
            ])
            import jax
            assert jax.process_count() == 2, jax.process_count()
            assert len(jax.devices()) == 8, jax.devices()
            assert len(jax.local_devices()) == 4
            w = broker.poll_weights()
            if jax.process_index() == 0:
                assert w is not None, "primary must have published"
            else:
                assert w is None, "non-primary must NOT publish"
            print("MULTIHOST2_OK pid={pid}")
            """
        )

    outs = _run_two_processes(script)
    for pid, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"process {pid}: {err[-2000:]}"
        assert f"MULTIHOST2_OK pid={pid}" in out, (out, err[-2000:])
