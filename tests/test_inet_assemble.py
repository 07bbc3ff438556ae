"""In-network batch assembly (ISSUE 20): DTB1 block wire goldens, the
shard-side RowAssembler vs the learner's own pack, bitwise staged
parity through real armed shards, the --broker.assemble=false
inertness pin, and the assembly-station conservation ledger.

The committed INET_PACK_AB.json (scripts/ab_inet_pack.py) is the full
acceptance artifact — shard splits {1,2,3,4} x DTR1/2/3 x both packers
plus the host-cost collapse; the tier-1 tests here pin the wire layout,
one end-to-end parity arm, the off-by-default contract, and the ledger
identity, and a nightly+slow wrapper re-runs the A/B."""

import hashlib
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from dotaclient_tpu.transport.base import RetryPolicy, connect
from dotaclient_tpu.transport.serialize import (
    AssembledRow,
    BlockSpec,
    block_spec_flags,
    cast_rollout_obs_bf16,
    deserialize_block,
    peek_block_spec,
    serialize_block,
    serialize_rollout,
)
from dotaclient_tpu.transport.tcp import BrokerServer, TcpBroker

from tests.test_transport import make_rollout

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAST = RetryPolicy(window_s=0.4, backoff_base_s=0.01, backoff_cap_s=0.05, jitter=0.0)


# --- DTB1 block golden bytes --------------------------------------------
#
# serialize.py's module docstring is the wire SPEC; this freezes the
# block layout the way the DTR/DTW goldens freeze the frame layouts.
# The synthetic block is tiny (2 rows x 8 payload bytes), so the WHOLE
# block is pinned as exact hex — header, both sidecars, both payloads.
#
# _BLK header:  44544231   magic b'DTB1'
#               01         u8 fmt=1
#               0200       u16 n_rows=2
#               0200 0300  u16 T=2, u16 H=3
#               02         u8 flags=2 (bit1 obs_bf16)
#               08000000   u32 row_bytes=8
#               44332211   u32 layout_crc=0x11223344
# then one 52-byte _BLK_SIDE sidecar per row (version, actor_id,
# episode_return f32, trace_id u64, birth_time f64, priority f32,
# boot u64, epoch u32, seq u32, row_flags u32 bit0=last_done),
# then the row payloads back to back.
BLOCK_GOLDEN_SPEC = BlockSpec(
    seq_len=2, lstm_hidden=3, with_aux=False, obs_bf16=True,
    row_bytes=8, layout_crc=0x11223344,
)
BLOCK_GOLDEN_HEADER_HEX = "4454423101020002000300020800000044332211"
BLOCK_GOLDEN_HEX = (
    "4454423101020002000300020800000044332211"
    # row 0 sidecar: version=7 actor=11 ep_ret=1.25 trace=0xDEADBEEF...
    # birth=1.75e9 priority=0.5 boot=0x0102030405060708 epoch=9 seq=21
    # row_flags=1 (last_done)
    "070000000b0000000000a03f0df0fecaefbeadde00000060b813da41"
    "0000003f0807060504030201090000001500000001000000"
    # row 1 sidecar: version=8 actor=12, everything else zero (44 bytes)
    "080000000c000000" + "00" * 44
    # payloads: row 0 = bytes(0..7), row 1 = 8 x 0xff
    + "0001020304050607ffffffffffffffff"
)


def _golden_rows():
    return [
        AssembledRow(
            payload=bytes(range(8)), version=7, actor_id=11,
            episode_return=1.25, trace_id=0xDEADBEEFCAFEF00D,
            birth_time=1.75e9, priority=0.5, boot=0x0102030405060708,
            epoch=9, seq=21, last_done=True,
        ),
        AssembledRow(payload=b"\xff" * 8, version=8, actor_id=12),
    ]


def test_dtb1_block_golden_bytes():
    data = serialize_block(BLOCK_GOLDEN_SPEC, _golden_rows())
    assert block_spec_flags(BLOCK_GOLDEN_SPEC) == 2
    assert data[:20].hex() == BLOCK_GOLDEN_HEADER_HEX
    assert data.hex() == BLOCK_GOLDEN_HEX


def test_dtb1_block_roundtrip_and_rejects():
    data = serialize_block(BLOCK_GOLDEN_SPEC, _golden_rows())
    assert peek_block_spec(data) == BLOCK_GOLDEN_SPEC
    spec, rows = deserialize_block(data)
    assert spec == BLOCK_GOLDEN_SPEC
    assert len(rows) == 2
    r0, r1 = rows
    assert r0.payload == bytes(range(8)) and r0.last_done
    assert (r0.version, r0.actor_id, r0.trace_id) == (7, 11, 0xDEADBEEFCAFEF00D)
    assert (r0.boot, r0.epoch, r0.seq) == (0x0102030405060708, 9, 21)
    assert abs(r0.episode_return - 1.25) < 1e-6 and abs(r0.priority - 0.5) < 1e-6
    assert r1.payload == b"\xff" * 8 and not r1.last_done
    # empty block roundtrips (the GET_BLOCK timeout-expired reply)
    spec0, rows0 = deserialize_block(serialize_block(BLOCK_GOLDEN_SPEC, []))
    assert spec0 == BLOCK_GOLDEN_SPEC and rows0 == []
    # rejects: not-a-block, truncation, payload/row_bytes mismatch
    assert peek_block_spec(b"garbage") is None
    with pytest.raises(ValueError):
        deserialize_block(data[: len(data) - 3])
    with pytest.raises(ValueError):
        serialize_block(BLOCK_GOLDEN_SPEC, [AssembledRow(payload=b"short", version=0)])


# --- shard assembler vs learner pack ------------------------------------


def _mixed_frames(n=6, T=8, H=8):
    """Partial-length frames over all three rollout wires with distinct
    actor ids — the adversarial mix the A/B's parity section uses."""
    frames = []
    for i in range(n):
        L = 3 + (i % (T - 3))
        r = make_rollout(L=L, H=H, version=0, actor_id=100 + i, seed=i)
        if i % 3 == 1:
            r = r._replace(trace_id=0x1000 + i, birth_time=1.5 + i)
        elif i % 3 == 2:
            r = cast_rollout_obs_bf16(r)
        frames.append(serialize_rollout(r))
    return frames


def test_row_assembler_native_python_identical():
    """The C fast path and the python fill fallback produce byte-equal
    rows for every wire (DTR1/DTR2/DTR3) and partial lengths — the same
    single-row encoder contract the packers already pin, restated for
    the shard tier."""
    from dotaclient_tpu import native
    from dotaclient_tpu.transport.assemble import RowAssembler

    if native.load_packer() is None:
        pytest.skip("native packer unavailable")
    T, H = 8, 8
    asm_c = RowAssembler(T, H, False, obs_bf16=False, use_native=True)
    asm_py = RowAssembler(T, H, False, obs_bf16=False, use_native=False)
    assert asm_c.spec == asm_py.spec
    for f in _mixed_frames(T=T, H=H):
        rc = asm_c.assemble(f, priority=0.25)
        rp = asm_py.assemble(f, priority=0.25)
        assert bytes(rc.payload) == bytes(rp.payload)
        assert (rc.version, rc.actor_id, rc.last_done) == (
            rp.version, rp.actor_id, rp.last_done,
        )


def _row_hashes(groups, n_rows):
    if isinstance(groups, dict):
        rows = [
            b"".join(
                np.ascontiguousarray(groups[k][r]).view(np.uint8).tobytes()
                for k in sorted(groups)
            )
            for r in range(n_rows)
        ]
    else:
        rows = [np.ascontiguousarray(groups[r]).tobytes() for r in range(n_rows)]
    return sorted(hashlib.sha256(r).hexdigest() for r in rows)


def test_assembled_staging_bitwise_parity():
    """End-to-end tentpole leg in tier-1: two REAL armed shards behind
    the REAL FabricBroker block fan-in into an assembled StagingBuffer
    produce a staged batch whose rows are bitwise identical to the
    classic learner-host pack of the SAME wire bytes (sorted per-row
    hashes: fan-in order is nondeterministic, row content is the
    contract). The full split/packer matrix is the committed
    INET_PACK_AB.json."""
    import jax

    from dotaclient_tpu.config import LearnerConfig, PolicyConfig
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.fused_io import FusedBatchIO
    from dotaclient_tpu.parallel.train_step import _batch_template
    from dotaclient_tpu.runtime.staging import StagingBuffer, cast_obs_to_compute_dtype
    from dotaclient_tpu.transport import memory as mem
    from dotaclient_tpu.transport.fabric import FabricBroker

    B, T, H = 6, 8, 8
    frames = _mixed_frames(n=B, T=T, H=H)

    def cfg_io(assemble):
        cfg = LearnerConfig(
            batch_size=B, seq_len=T,
            policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=H, mlp_hidden=16),
        )
        cfg.staging.assemble = assemble
        template = cast_obs_to_compute_dtype(
            cfg, jax.tree.map(np.asarray, _batch_template(cfg))
        )
        return cfg, FusedBatchIO(template, mesh_lib.make_mesh("dp=-1"))

    def finish(sb):
        batch, groups = sb.get_batch_groups(timeout=30.0)
        assert batch is not None, sb.stats()
        hashes = _row_hashes(groups, B)
        lease = sb.last_batch_lease
        if lease is not None:
            lease.release()
        return hashes

    # assembled arm: armed shards -> block fan-in -> concat landing
    servers = [BrokerServer(port=0, assemble=True).start() for _ in range(2)]
    eps = [f"tcp://127.0.0.1:{s.port}" for s in servers]
    fab = FabricBroker(eps, retry=FAST)
    cfg, io = cfg_io(True)
    sb = StagingBuffer(cfg, fab, version_fn=lambda: 0, fused_io=io)
    sb.start()
    try:
        for f in frames:
            fab.publish_experience(f)
        asm_hashes = finish(sb)
        asm_stats = sb.stats()
    finally:
        sb.stop()
        fab.close()
        for s in servers:
            s.stop()
    # assembled mode runs NO host pack pool and meters its landing
    assert asm_stats["rows_packed"] == B
    assert "pack_wall_s" in asm_stats and "pack_ring_occupancy" in asm_stats

    # classic arm: the HEAD learner-host pack of the same bytes
    mem.reset("inet_parity")
    pub = connect("mem://inet_parity")
    for f in frames:
        pub.publish_experience(f)
    cfg, io = cfg_io(False)
    sb = StagingBuffer(
        cfg, connect("mem://inet_parity"), version_fn=lambda: 0, fused_io=io
    )
    sb.start()
    try:
        classic_hashes = finish(sb)
    finally:
        sb.stop()

    assert asm_hashes == classic_hashes


def test_staging_assemble_config_validation():
    """--staging.assemble hard-fails at CONSTRUCTION on an unusable
    topology (no fused H2D, a pack pool, a broker with no block op) —
    never silently falls back to the classic pack."""
    from dotaclient_tpu.config import LearnerConfig, PolicyConfig
    from dotaclient_tpu.runtime.staging import StagingBuffer
    from dotaclient_tpu.transport import memory as mem

    cfg = LearnerConfig(
        batch_size=4, seq_len=8,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16),
    )
    cfg.staging.assemble = True
    mem.reset("inet_cfg")
    with pytest.raises(ValueError, match="fused"):
        StagingBuffer(cfg, connect("mem://inet_cfg"), version_fn=lambda: 0)
    class _FakeIO:
        row_bytes = 64
        layout = None
    cfg.staging.pack_workers = 4
    with pytest.raises(ValueError, match="pack_workers"):
        StagingBuffer(
            cfg, connect("mem://inet_cfg"), version_fn=lambda: 0, fused_io=_FakeIO()
        )
    cfg.staging.pack_workers = 1
    # mem:// serves no DTB1 block op -> refused up front
    with pytest.raises(ValueError, match="DTB1"):
        StagingBuffer(
            cfg, connect("mem://inet_cfg"), version_fn=lambda: 0, fused_io=_FakeIO()
        )


# --- default-off inertness ----------------------------------------------


def test_broker_assemble_default_off_inert_subprocess():
    """The k8s pin (--broker.assemble=false) is byte-for-byte HEAD: an
    unarmed BrokerServer round-trips classic publish/consume payloads
    exactly, keeps every assemble counter absent from its ledger
    surface at zero, and never imports the assemble machinery (module,
    jax). Subprocess so the import-surface assertion is structural."""
    from tests.conftest import clean_subprocess_env

    code = """
import sys, time
from dotaclient_tpu.transport.tcp import BrokerServer
from dotaclient_tpu.transport.base import connect

srv = BrokerServer(port=0).start()  # default: assemble OFF
assert srv.assemble is False and srv._asm_meta is None
cli = connect(f"tcp://127.0.0.1:{srv.port}")
payloads = [bytes([65 + i]) * (100 + i) for i in range(5)]
for p in payloads:
    cli.publish_experience(p)
got = []
t0 = time.time()
while len(got) < len(payloads) and time.time() - t0 < 20:
    got.extend(cli.consume_experience(max_items=8, timeout=1.0))
assert sorted(got) == sorted(payloads), "classic roundtrip bytes changed"
led = srv.assemble_ledger()
assert all(v == 0 for v in led.values()), led
assert "dotaclient_tpu.transport.assemble" not in sys.modules
assert "jax" not in sys.modules, "unarmed broker pulled in jax"
srv.stop()
print("INERT_OK")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=120,
        env=clean_subprocess_env(),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "INERT_OK" in proc.stdout


def test_get_block_against_unarmed_shard_is_refused():
    """Flipping --staging.assemble against a shard that is not armed is
    a HARD failure (connection kill on the unknown-op precedent), never
    a hung learner."""
    from dotaclient_tpu.transport.assemble import RowAssembler

    srv = BrokerServer(port=0).start()
    try:
        cli = TcpBroker("127.0.0.1", srv.port, retry=FAST)
        spec = RowAssembler(8, 8, False, obs_bf16=False, use_native=False).spec
        with pytest.raises((ConnectionError, OSError)):
            cli.consume_block(spec, max_rows=4, timeout=0.2)
    finally:
        srv.stop()


# --- conservation ledger ------------------------------------------------


def _ledger_balanced(led):
    return led["rows_admitted"] == (
        led["rows_packed"] + led["rows_reject"] + led["rows_bypassed"]
        + led["rows_dropped"] + led["rows_resident"]
    )


def test_assemble_conservation_ledger_partial_drain_and_kill():
    """The assembly-station ledger identity — admitted = packed +
    reject + bypassed + dropped + resident — holds at EVERY quiescent
    point of an armed shard's life: pre-spec backlog, partial block
    serves (resident rows remain), a malformed admit (reject at pack),
    classic CONSUME bypass, drop-oldest overflow, and a kill with rows
    still resident (they stay accounted in the final snapshot, never
    leaked as consumed-by-nobody)."""
    from dotaclient_tpu.transport.assemble import RowAssembler

    T, H = 8, 8
    spec = RowAssembler(T, H, False, obs_bf16=False, use_native=False).spec
    srv = BrokerServer(port=0, assemble=True, assemble_native=False, maxlen=16).start()
    try:
        cli = TcpBroker("127.0.0.1", srv.port, retry=FAST)
        frames = _mixed_frames(n=6, T=T, H=H)
        # 5 good + 1 garbage land BEFORE the first GET_BLOCK: all stay
        # un-packed backlog (no spec yet), resident and balanced.
        for f in frames[:5]:
            cli.publish_experience(f)
        cli.publish_experience(b"not a rollout frame")
        t0 = time.monotonic()
        while srv.assemble_ledger()["rows_admitted"] < 6:
            assert time.monotonic() - t0 < 10
            time.sleep(0.01)
        led = srv.assemble_ledger()
        assert led["rows_resident"] == 6 and led["rows_packed"] == 0
        assert _ledger_balanced(led)

        # partial serve: 3 rows leave (FIFO -> all good), 3 stay resident
        spec1, rows1 = deserialize_block(cli.consume_block(spec, 3, timeout=5.0))
        assert spec1 == spec and len(rows1) == 3
        led = srv.assemble_ledger()
        assert led["rows_packed"] == 3 and led["rows_resident"] == 3
        assert led["blocks_built"] == 1
        assert _ledger_balanced(led)
        # blocks_served increments after the reply WRITE completes, so
        # the client can hold the block a beat before the counter ticks
        t0 = time.monotonic()
        while srv.assemble_ledger()["blocks_served"] < 1:
            assert time.monotonic() - t0 < 10
            time.sleep(0.01)

        # classic CONSUME against the armed shard: bypass, still balanced
        got = cli.consume_experience(max_items=1, timeout=5.0)
        assert got == [frames[3]]
        led = srv.assemble_ledger()
        assert led["rows_bypassed"] == 1 and _ledger_balanced(led)

        # drain the rest: the garbage frame rejects AT PACK, good row serves
        spec2, rows2 = deserialize_block(cli.consume_block(spec, 8, timeout=5.0))
        assert len(rows2) == 1  # frames[4]; the garbage frame was rejected
        led = srv.assemble_ledger()
        assert led["rows_reject"] == 1 and led["rows_resident"] == 0
        assert led["rows_packed"] == 4 and _ledger_balanced(led)

        # eager-packed admits (assembler now live) + kill with residents
        for f in frames[:3]:
            cli.publish_experience(f)
        t0 = time.monotonic()
        while srv.assemble_ledger()["rows_resident"] < 3:
            assert time.monotonic() - t0 < 10
            time.sleep(0.01)
        led = srv.assemble_ledger()
        assert led["rows_resident"] == 3 and _ledger_balanced(led)
        assert led["cpu_s"] > 0.0
    finally:
        srv.stop()
    # post-kill snapshot: the 3 resident rows died WITH the shard,
    # accounted as resident in its final ledger — nothing unaccounted.
    led = srv.assemble_ledger()
    assert _ledger_balanced(led)


# --- the committed acceptance artifact ----------------------------------


def test_inet_pack_ab_artifact_verdict():
    """Guard the COMMITTED INET_PACK_AB.json: bitwise-identical staged
    batches for every shard split on both packers, the off-pin proven
    inert, and the collapse verdict — pack_over_concat_x >= 2 wherever
    the independent GIL-released memcpy probe shows the host can
    express a copy-throughput advantage; on bandwidth-starved hosts the
    raw ratio is committed and excused BY THE PROBE, in-artifact (the
    PACK_SCALE_AB disclosure pattern)."""
    path = pathlib.Path(REPO_ROOT) / "INET_PACK_AB.json"
    data = json.loads(path.read_text())
    v = data["verdict"]
    assert v["all_green"], v
    assert v["assembled_bitwise_identical"] and v["assemble_off_inert"]
    parity = data["parity"]
    assert parity["all_identical"]
    for packer in ("native", "python"):
        arms = parity[packer]["assembled"]
        assert set(arms) == {"shards_1", "shards_2", "shards_3", "shards_4"}
        assert all(a["bitwise_identical"] for a in arms.values()), arms
    # the probe-keyed collapse judgment, exactly as the script computes
    if v["host_can_express_parallel_copy"]:
        assert v["pack_over_concat_x"] >= 2.0
    else:
        assert data["host_memcpy_probe"]["copy_scaling_4t"] < 1.5
        assert v["collapse_caveat"]


@pytest.mark.nightly
@pytest.mark.slow  # nightly AND slow: the tier-1 -m 'not slow' override
def test_ab_inet_pack_quick_nightly(tmp_path):
    """Re-run the in-network-assembly A/B (--quick) in a clean
    subprocess and assert the committed-artifact schema + verdict
    invariants live. On a capable host (memcpy probe >= 1.5x at 4
    threads) this REQUIRES the full >= 2x collapse bar — the bar arms
    itself on real learner-class hardware."""
    from tests.conftest import clean_subprocess_env

    script = pathlib.Path(REPO_ROOT) / "scripts" / "ab_inet_pack.py"
    out = tmp_path / "inet_ab.json"
    proc = subprocess.run(
        [sys.executable, str(script), "--quick", "--out", str(out)],
        capture_output=True,
        text=True,
        timeout=570,
        env=clean_subprocess_env(extra={"JAX_PLATFORMS": "cpu"}),
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    data = json.loads(out.read_text())
    for key in ("parity", "host_cost", "host_memcpy_probe", "off_inert", "verdict"):
        assert key in data, key
    v = data["verdict"]
    assert v["all_green"], v
    assert v["assembled_bitwise_identical"] and v["assemble_off_inert"]
    if v["host_can_express_parallel_copy"]:
        assert v["pack_over_concat_x"] >= 2.0
