"""chip_smoke.py rehearsed without a chip: its phases called at a tiny
policy with the expected platform "cpu" (the steering — platform, sizes,
fleet width — happens here; the script's only option stays --chips),
and the plain script failing fast where JAX finds no TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import clean_subprocess_env
from dotaclient_tpu.config import LearnerConfig, PolicyConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

SMALL = (
    "--policy.unit_embed_dim", "16",
    "--policy.lstm_hidden", "16",
    "--policy.mlp_hidden", "16",
)  # fmt: skip

# The learner phase, steered small, run in a process of its own so that
# it can also say whether the PARENT of the fleet ever initialised a JAX
# backend (under pytest one already is).
_LEARNER_PHASE = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke
chip_smoke.ACTOR_PROCS, chip_smoke.ENVS_PER_ACTOR = 1, 4
out = chip_smoke.learner_phase(
    {workdir!r}, platform="cpu", lstm_impl="scan", steps=6, policy_flags={small!r},
    learner_flags=("--batch_size", "8"), timeout_s=240.0,
)
from jax._src import xla_bridge
out["parent_backends_initialised"] = xla_bridge.backends_are_initialized()
print(json.dumps(out))
"""


@pytest.fixture
def one_device_children(monkeypatch):
    """Children of the phases inherit os.environ: give them the
    one-device CPU topology a deployed binary sees."""
    for k, v in clean_subprocess_env().items():
        monkeypatch.setenv(k, v)


def test_learner_phase_small_on_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _LEARNER_PHASE.format(root=REPO_ROOT, workdir=str(tmp_path), small=SMALL)],
        capture_output=True,
        text=True,
        timeout=300,
        env=clean_subprocess_env(),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert out["steps"] == 6 and len(out["losses"]) == 6
    assert out["lstm_impl"] == "scan" and out["lstm_impl_asked"] == "auto"
    assert out["packer"] in ("native", "python")
    assert out["wire_frames_consumed"] >= 6 * 8
    assert out["weights_published"] >= 1 and out["actor_weight_version_seen"] >= 1
    assert out["compile_s"] > 0 and out["warm_step_s_median"] > 0
    assert out["compile_cache"]["hits"] + out["compile_cache"]["misses"] >= 1
    # one process per chip: the fleet's parent must stay off the device
    assert out["parent_backends_initialised"] is False


def test_serve_phase_small_on_cpu(tmp_path, one_device_children):
    out = chip_smoke.serve_phase(str(tmp_path), platform="cpu", steps=64, policy_flags=SMALL)
    assert out["device"]["platform"] == "cpu"
    assert out["served_steps"] >= 64
    assert out["carries_resident"] == out["envs"] == chip_smoke.SERVE_ENVS
    assert out["bad_requests"] == 0


def test_multichip_phase_dp4_virtual_devices(capsys):
    """The --chips 4 comparison on four virtual CPU devices, with the
    kernel in interpret mode so that the shard_map wrapping over dp is
    what runs: dp=4 losses agree with one device, batch shards sit on
    four devices, parameters are replicated."""
    policy = PolicyConfig(
        unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, lstm_impl="pallas_interpret"
    )
    cfg = LearnerConfig(batch_size=8, seq_len=4, policy=policy)
    out = chip_smoke.multichip_phase(
        4, platform="cpu", lstm_impl="pallas_interpret", cfg=cfg, steps=3
    )
    assert out["dp"]["devices"] == 4 and out["one_device"]["devices"] == 1
    assert out["dp"]["batch_shard_devices"] == 4 and out["dp"]["batch_shard_rows"] == [2]
    assert out["dp"]["params_replicated_on_all"]
    assert out["dp"]["all_reduces_in_program"] > 0
    assert out["one_device"]["all_reduces_in_program"] == 0
    assert out["max_abs_loss_diff"] <= chip_smoke.LOSS_ATOL + chip_smoke.LOSS_RTOL * 10
    # the phase printed its own JSON line
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["phase"] == "chips4"


def test_multichip_phase_refuses_everything_on_one_device():
    """Fewer devices than asked for is a failure, not a smaller mesh."""
    with pytest.raises(chip_smoke.SmokeFailure, match="needs 64 cpu devices"):
        chip_smoke.multichip_phase(64, platform="cpu")


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_script_fails_fast_without_a_tpu(argv, tmp_path):
    """Plain `python chip_smoke.py` (and --chips 4) where JAX finds no
    accelerator: non-zero exit in seconds, `"ok": false` last, and no
    phase result printed."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"), *argv],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(tmp_path),
        env=clean_subprocess_env(extra={"JAX_PLATFORMS": "cpu"}),
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["ok"] is False
    assert not any('"phase"' in line for line in lines)
