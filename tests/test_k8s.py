"""Deployment-shell validation (SURVEY.md §1 L6, §3.5) without a cluster.

kubectl isn't in the image, so this is the CI-style stand-in for
`kubectl apply --dry-run=client -f k8s/`: parse every manifest, check the
schema shape k8s would reject, and cross-check the wiring that a dry-run
can't see — that every container command is a real module in this repo,
that every --flag it passes is a real config field, and that broker URLs
point at a Service that exists.
"""

import json
import pathlib
import re
import subprocess
import sys

import pytest
import yaml

K8S = pathlib.Path(__file__).resolve().parent.parent / "k8s"

MANIFESTS = sorted(K8S.glob("*.yaml"))


def _docs():
    out = []
    for path in MANIFESTS:
        for doc in yaml.safe_load_all(path.read_text()):
            if doc is not None:
                out.append((path.name, doc))
    return out


DOCS = _docs()


def test_manifests_exist():
    names = {p.name for p in MANIFESTS}
    assert {
        "broker.yaml",
        "learner.yaml",
        "learner-multihost.yaml",
        "actors.yaml",
        "evaluator.yaml",
        "rabbitmq.yaml",
        "inference.yaml",
        "control.yaml",
        "league.yaml",
        "fleetd.yaml",
    } <= names
    assert (K8S / "Dockerfile").exists()


@pytest.mark.parametrize("fname,doc", DOCS, ids=lambda v: v if isinstance(v, str) else "")
def test_doc_schema_shape(fname, doc):
    assert doc.get("apiVersion"), f"{fname}: missing apiVersion"
    kind = doc.get("kind")
    assert kind in ("Deployment", "StatefulSet", "Service"), f"{fname}: kind {kind}"
    assert doc["metadata"].get("name"), f"{fname}: missing metadata.name"
    spec = doc.get("spec")
    assert spec, f"{fname}: missing spec"
    if kind in ("Deployment", "StatefulSet"):
        sel = spec["selector"]["matchLabels"]
        labels = spec["template"]["metadata"]["labels"]
        assert sel.items() <= labels.items(), f"{fname}: selector doesn't match pod labels"
        containers = spec["template"]["spec"]["containers"]
        assert containers, f"{fname}: no containers"
        for c in containers:
            assert c.get("image"), f"{fname}: container {c.get('name')} has no image"
            assert c.get("resources", {}).get("requests"), (
                f"{fname}: container {c.get('name')} has no resource requests"
            )


def _our_containers():
    """(fname, container) for every container running this package's image."""
    for fname, doc in DOCS:
        if doc["kind"] == "Service":
            continue
        for c in doc["spec"]["template"]["spec"]["containers"]:
            if c["image"].startswith("dotaclient-tpu"):
                yield fname, c


def test_commands_are_real_modules():
    for fname, c in _our_containers():
        cmd = c.get("command")
        if cmd is None:  # Dockerfile default CMD
            continue
        assert cmd[0] == "python" and cmd[1] == "-m", f"{fname}: {cmd}"
        module = cmd[2]
        proc = subprocess.run(
            [sys.executable, "-c", f"import importlib.util as u; exit(0 if u.find_spec({module!r}) else 1)"],
            cwd=K8S.parent,
        )
        assert proc.returncode == 0, f"{fname}: module {module} not importable"


def test_flags_are_real_config_fields():
    from dotaclient_tpu.config import ActorConfig, EvalConfig, LearnerConfig, add_flags
    import argparse

    from dotaclient_tpu.config import (
        ControlConfig,
        FleetConfig,
        InferenceConfig,
        LeagueConfig,
    )

    known = {
        "dotaclient_tpu.runtime.learner": LearnerConfig(),
        "dotaclient_tpu.runtime.actor": ActorConfig(),
        "dotaclient_tpu.eval.evaluator": EvalConfig(),
        "dotaclient_tpu.serve.server": InferenceConfig(),
        "dotaclient_tpu.control.server": ControlConfig(),
        "dotaclient_tpu.league.server": LeagueConfig(),
        "dotaclient_tpu.obs.fleetd": FleetConfig(),
    }
    for fname, c in _our_containers():
        cmd = c.get("command")
        if cmd is None or cmd[2] not in known:
            continue
        parser = argparse.ArgumentParser()
        add_flags(parser, known[cmd[2]])
        # parse_args would sys.exit on an unknown flag; that's the assert
        parser.parse_args(c.get("args", []))


def test_broker_urls_resolve_to_a_service():
    services = {doc["metadata"]["name"] for _, doc in DOCS if doc["kind"] == "Service"}
    url_re = re.compile(r"^(tcp|amqp)://(?:[^@/]+@)?([^:/]+)")
    found = 0
    for fname, c in _our_containers():
        args = c.get("args", [])
        for flag, val in zip(args, args[1:]):
            if flag.endswith("broker_url"):
                # a comma list is the broker fabric: every shard must
                # resolve; per-pod DNS (pod-i.service) resolves through
                # its headless Service, the PR-10 affinity pattern
                for url in val.split(","):
                    host = url_re.match(url.strip()).group(2)
                    svc = host.split(".", 1)[1] if "." in host else host
                    assert svc in services, f"{fname}: broker host {host!r} has no Service"
                found += 1
    assert found >= 3  # learner + actor + evaluator all wired


def test_learner_requests_tpu():
    (fname, doc), = [(f, d) for f, d in DOCS if d["metadata"]["name"] == "learner" and d["kind"] != "Service"]
    c = doc["spec"]["template"]["spec"]["containers"][0]
    assert c["resources"]["requests"].get("google.com/tpu"), "learner must request TPU chips"
    sel = doc["spec"]["template"]["spec"].get("nodeSelector", {})
    assert any("tpu" in k for k in sel), "learner must pin to the TPU node pool"


def test_multihost_learner_slice_consistency():
    """The multi-host manifest must form a coherent slice: one pod per
    host (replicas > 1, Parallel start so the cluster can assemble), a
    TPU nodeSelector, the --multihost flag, and a headless Service of
    the same name for per-pod DNS (cluster formation)."""
    (_, doc), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "learner-multihost" and d["kind"] == "StatefulSet"
    ]
    assert doc["spec"]["replicas"] > 1
    assert doc["spec"].get("podManagementPolicy") == "Parallel"
    pod = doc["spec"]["template"]["spec"]
    c = pod["containers"][0]
    assert c["resources"]["requests"].get("google.com/tpu")
    assert any("tpu" in k for k in pod.get("nodeSelector", {}))
    args = c.get("args", [])
    assert "--multihost" in args and args[args.index("--multihost") + 1] == "true"
    svc = [
        d for f, d in DOCS
        if d["kind"] == "Service" and d["metadata"]["name"] == doc["spec"]["serviceName"]
    ]
    assert svc, "multihost StatefulSet's serviceName must reference a defined Service"
    # k8s headless convention: the literal string "None" (YAML `None` is
    # a plain string, which is exactly what the API expects here).
    assert svc[0]["spec"].get("clusterIP") == "None", (
        "multihost Service must be HEADLESS (clusterIP: None) for per-pod DNS"
    )


def test_learner_manifests_keep_pipelined_loop():
    """Production learner deploys pin --obs.step_phases true — phase
    attribution costs the loop nothing (obs/compute.py fences the
    prefetch lane, never the loop) and exports the pipeline_* overlap
    scoreboard — and their args parse: the learner's parser refuses a
    flag it does not know (those that went with the serial loop and the
    grouped layout among them), so such a pod would crash-loop at
    boot."""
    from dotaclient_tpu.config import LearnerConfig, parse_config

    for name in ("learner", "learner-multihost"):
        (_, doc), = [
            (f, d) for f, d in DOCS
            if d["metadata"]["name"] == name and d["kind"] != "Service"
        ]
        args = doc["spec"]["template"]["spec"]["containers"][0]["args"]
        assert "--obs.step_phases" in args, f"{name}: step_phases not pinned"
        assert args[args.index("--obs.step_phases") + 1] == "true", (
            f"{name}: step_phases costs the loop nothing and carries the "
            "pipeline_* scoreboard — pin it on"
        )
        assert parse_config(LearnerConfig(), args).obs.step_phases is True


def test_learner_drain_grace_pairing():
    """Preemption drain arithmetic (PR 7): every learner manifest must
    arm the SIGTERM drain and pair it with a terminationGracePeriod that
    covers preStop + the drain budget with margin — otherwise the
    kubelet SIGKILLs a mid-save learner exactly when durability matters
    most."""
    for name in ("learner", "learner-multihost"):
        (_, doc), = [
            (f, d) for f, d in DOCS
            if d["metadata"]["name"] == name and d["kind"] != "Service"
        ]
        pod = doc["spec"]["template"]["spec"]
        c = pod["containers"][0]
        args = c["args"]
        assert args[args.index("--ckpt.drain_on_sigterm") + 1] == "true", (
            f"{name}: SIGTERM drain not armed"
        )
        assert args[args.index("--ckpt.full_state") + 1] == "true", (
            f"{name}: drain without full_state would lose reservoir/pending state"
        )
        budget = float(args[args.index("--ckpt.drain_budget_s") + 1])
        grace = pod.get("terminationGracePeriodSeconds")
        assert grace is not None, f"{name}: no terminationGracePeriodSeconds"
        prestop = c.get("lifecycle", {}).get("preStop", {}).get("exec", {}).get("command")
        assert prestop and prestop[0] == "sleep", f"{name}: preStop sleep missing"
        prestop_s = float(prestop[1])
        assert grace >= budget + prestop_s + 5, (
            f"{name}: grace {grace}s must cover preStop {prestop_s}s + "
            f"drain budget {budget}s + margin"
        )


def test_broker_ships_admission_watermarks():
    """Every production broker shard must run with load-shed armed:
    shed_high below the drop-oldest bound (overload surfaces at
    producers, not as silent oldest-frame loss) and a real hysteresis
    band under it."""
    (_, doc), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "broker" and d["kind"] in ("Deployment", "StatefulSet")
    ]
    args = doc["spec"]["template"]["spec"]["containers"][0]["args"]
    vals = {k: int(args[args.index(k) + 1]) for k in ("--maxlen", "--shed_high", "--shed_low")}
    assert 0 < vals["--shed_low"] < vals["--shed_high"] < vals["--maxlen"]


def test_broker_fabric_statefulset_and_shard_lists_match_replicas():
    """The broker fabric (PR 14), GATED on the committed
    BROKER_FABRIC_SOAK verdict (the WIRE_SOAK flip pattern): the broker
    is a StatefulSet of fabric-shard pods behind a HEADLESS Service
    (per-pod DNS is the shard identity clients hash against), priority
    admission is armed, and EVERY --broker_url shard list in the fleet
    names exactly one endpoint per replica, in per-pod DNS form — a
    list/replica mismatch would silently re-route every key's
    rendezvous hash."""
    verdict = json.loads((K8S.parent / "BROKER_FABRIC_SOAK.json").read_text())["verdict"]
    assert verdict["all_green"] is True, (
        "the fabric manifests require a green BROKER_FABRIC_SOAK verdict"
    )
    (_, doc), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "broker" and d["kind"] != "Service"
    ]
    assert doc["kind"] == "StatefulSet"
    assert doc["spec"]["serviceName"] == "broker"
    replicas = int(doc["spec"]["replicas"])
    assert replicas >= 2
    c = doc["spec"]["template"]["spec"]["containers"][0]
    assert c["command"][2] == "dotaclient_tpu.transport.fabric"
    args = c["args"]
    assert args[args.index("--priority") + 1] == "true"
    (_, svc), = [
        (f, d) for f, d in DOCS
        if d["kind"] == "Service" and d["metadata"]["name"] == "broker"
    ]
    assert svc["spec"].get("clusterIP") == "None", "fabric needs a HEADLESS service"
    expect = ",".join(f"tcp://broker-{i}.broker:13370" for i in range(replicas))
    lists = 0
    for fname, cc in _our_containers():
        cargs = cc.get("args", [])
        for flag, val in zip(cargs, cargs[1:]):
            if flag.endswith("broker_url"):
                assert val == expect, f"{fname}: shard list {val!r} != {expect!r}"
                lists += 1
    assert lists >= 4  # learner, multihost learner, actors, evaluator, serve


def test_broker_assemble_pinned_off_with_ab_paper_trail():
    """In-network batch assembly (ISSUE 20): the shard fleet ships
    --broker.assemble EXPLICITLY pinned (the chaos-flag precedent) and
    the pin is OFF — the consumers-first rollout arms learners
    (--staging.assemble) before any shard pre-packs, and an unarmed
    shard is subprocess-proven byte-for-byte HEAD
    (tests/test_inet_assemble.py). The committed INET_PACK_AB verdict
    must be ALL GREEN regardless: it is the bitwise shard-pack parity
    proof a future flip rides on (the WIRE_SOAK flip pattern — changing
    this pin must touch the artifact too; MIGRATION item 20 is the
    rollout order, rollback = clear the flag)."""
    verdict = json.loads((K8S.parent / "INET_PACK_AB.json").read_text())["verdict"]
    assert verdict["all_green"] is True, (
        "the --broker.assemble pin requires a green INET_PACK_AB verdict"
    )
    (_, doc), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "broker" and d["kind"] != "Service"
    ]
    args = doc["spec"]["template"]["spec"]["containers"][0]["args"]
    assert "--broker.assemble" in args, "broker.assemble not pinned"
    assert args[args.index("--broker.assemble") + 1] == "false", (
        "assembly ships OFF until the learner fleet runs "
        "--staging.assemble (consumers-first; MIGRATION item 20)"
    )


def test_chaos_pinned_off_in_all_prod_manifests():
    """Chaos fault injection is a soak-only tool: every production
    container of this package that HAS the flag must pin it false, so a
    copy-pasted soak flag can never arm it in a fleet."""
    checked = 0
    for fname, c in _our_containers():
        cmd = c.get("command")
        if cmd is None or cmd[2] in (
            "dotaclient_tpu.transport.tcp_server",  # broker: no chaos surface
            "dotaclient_tpu.transport.fabric",  # fabric shard: no chaos surface
            "dotaclient_tpu.env.fake_dotaservice",  # env stub: no flags at all
            "dotaclient_tpu.serve.handoff",  # carry store: no chaos surface
            "dotaclient_tpu.control.server",  # control plane: no chaos surface
            "dotaclient_tpu.league.server",  # league service: no chaos surface
            "dotaclient_tpu.obs.fleetd",  # telemetry aggregator: no chaos surface
        ):
            continue
        args = c.get("args", [])
        flags = [a for a in args if a.endswith("chaos.enabled")]
        assert flags, f"{fname}: chaos.enabled not pinned"
        for flag in flags:
            assert args[args.index(flag) + 1] == "false", f"{fname}: chaos not pinned OFF"
        checked += 1
    assert checked >= 4  # learner, learner-multihost, actors, evaluator


def test_wire_obs_dtype_pinned_bf16_on_actors():
    """The quantized-wire flag ships EXPLICITLY pinned on the actor
    fleet (the chaos-flag precedent) — and since the bf16 soak signed
    off (WIRE_SOAK.json, all green: zero quarantines across f32/mixed/
    bf16 fleet states, meters walk with the fleet, 0.54x bytes/frame),
    the pin IS bf16: the fleet ships the quantized wire. This test is
    the flip's paper trail — changing the pin again must touch the soak
    verdict too. The broker stays wire-agnostic by design — it must NOT
    grow the flag (opaque bytes; no restart in the consumers-first
    upgrade)."""
    import json

    verdict = json.loads((K8S.parent / "WIRE_SOAK.json").read_text())["verdict"]
    assert verdict["ok"] is True, "bf16 pin requires a green WIRE_SOAK verdict"
    actor_containers = [
        (fname, c)
        for fname, c in _our_containers()
        if c.get("command") and c["command"][2] == "dotaclient_tpu.runtime.actor"
    ]
    assert actor_containers
    for fname, c in actor_containers:
        args = c.get("args", [])
        assert "--wire.obs_dtype" in args, f"{fname}: wire.obs_dtype not pinned"
        assert args[args.index("--wire.obs_dtype") + 1] == "bf16", (
            f"{fname}: the fleet ships the soak-approved bf16 wire"
        )
    for fname, c in _our_containers():
        if c.get("command") and c["command"][2] == "dotaclient_tpu.transport.tcp_server":
            assert "--wire.obs_dtype" not in c.get("args", []), (
                f"{fname}: the broker is wire-format agnostic; no wire flag"
            )


def test_inference_service_manifest():
    """The serving tier's deployment shell (PR 10 multi-replica): a
    StatefulSet behind a HEADLESS Service — carry residency demands
    replica affinity, so clients address replicas by per-pod DNS, never
    a load-balanced virtual IP — with probes on /healthz (liveness
    delayed past the boot compile), the broker weight subscription
    wired to the broker Service, and obs enabled so the serve_* scalars
    actually scrape."""
    (_, doc), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "inference" and d["kind"] == "StatefulSet"
    ]
    assert doc["spec"]["replicas"] >= 2, "multi-replica serving (PR 10)"
    assert doc["spec"]["serviceName"] == "inference"
    assert doc["spec"].get("podManagementPolicy") == "Parallel"
    c = doc["spec"]["template"]["spec"]["containers"][0]
    assert c["command"][2] == "dotaclient_tpu.serve.server"
    args = c["args"]
    # the weight-fanout subscription rides the same broker FABRIC shard
    # list the actors use (PR 14; the shard-list/replica cross-check
    # lives in test_broker_fabric_statefulset_and_shard_lists_match_replicas)
    assert args[args.index("--broker_url") + 1].startswith("tcp://broker-0.broker:13370,")
    assert args[args.index("--obs.enabled") + 1] == "true"
    mport = int(args[args.index("--obs.metrics_port") + 1])
    # probe PATHS are graftproto's SVC001 gate now (every httpGet path
    # is checked against the binary's actual served surface —
    # test_graftproto_covers_probes_and_grammars pins the coverage);
    # port agreement stays here, it's manifest-local wiring
    assert c["readinessProbe"]["httpGet"]["port"] == mport
    live = c["livenessProbe"]
    assert live["initialDelaySeconds"] >= 60, (
        "liveness must outwait the boot-time tick compile"
    )
    svc = [
        d for _, d in DOCS
        if d["kind"] == "Service" and d["metadata"]["name"] == "inference"
    ]
    assert svc, "inference StatefulSet needs its Service"
    assert svc[0]["spec"].get("clusterIP") == "None", (
        "inference Service must be HEADLESS: per-pod DNS is the affinity "
        "contract (a round-robin VIP would strand resident carries)"
    )
    ports = {p["port"] for p in svc[0]["spec"]["ports"]}
    sport = int(args[args.index("--serve.port") + 1])
    assert {sport, mport} <= ports


def test_serve_endpoint_lists_match_replicas_and_league_rides_serve():
    """Actor-side serve wiring (PR 10 + ISSUE 17), gated on a green
    SERVE_CHAOS_SOAK verdict (the WIRE_SOAK flip pattern): every fleet
    on the serve tier lists EXACTLY one per-pod DNS endpoint per
    inference replica (list drift = stranded capacity or a phantom
    endpoint); the scripted fleet adds the failover/fallback knobs; the
    league fleet — which used to be pinned EMPTY by the single-model
    refusal — now rides the multi-model tier and MUST pair the endpoint
    list with --serve.league naming the league Service (the pair is the
    contract: endpoint without league would trip the actor binary's
    refusal on boot)."""
    import json

    verdict = json.loads((K8S.parent / "SERVE_CHAOS_SOAK.json").read_text())["verdict"]
    bad = [k for k, v in verdict.items() if isinstance(v, bool) and not v]
    assert not bad, f"serve opt-in requires a green SERVE_CHAOS_SOAK verdict: {bad}"
    (_, sts), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "inference" and d["kind"] == "StatefulSet"
    ]
    replicas = sts["spec"]["replicas"]
    sts_args = sts["spec"]["template"]["spec"]["containers"][0]["args"]
    sport = sts_args[sts_args.index("--serve.port") + 1]
    expected = [f"inference-{i}.inference:{sport}" for i in range(replicas)]

    by_deploy = {}
    for fname, c in _our_containers():
        if c.get("command") and c["command"][2] == "dotaclient_tpu.runtime.actor":
            a = c.get("args", [])
            assert "--serve.endpoint" in a, f"{fname}: serve.endpoint not pinned"
            opp = a[a.index("--opponent") + 1]
            by_deploy[opp] = a

    league = by_deploy["league"]
    eps = league[league.index("--serve.endpoint") + 1].split(",")
    assert eps == expected, (
        f"league fleet endpoint list {eps} must name every inference "
        f"replica exactly: {expected}"
    )
    league_ep = league[league.index("--serve.league") + 1]
    assert league_ep, (
        "league fleet must name the league service: serve.endpoint "
        "without serve.league is the refused single-model combination"
    )
    svc = league_ep.split(":")[0]
    services = {d["metadata"]["name"] for _, d in DOCS if d["kind"] == "Service"}
    assert svc in services, f"--serve.league host {svc!r} has no Service"

    scripted = by_deploy["scripted_hard"]
    eps = scripted[scripted.index("--serve.endpoint") + 1].split(",")
    assert eps == expected, (
        f"scripted fleet endpoint list {eps} must name every inference "
        f"replica exactly: {expected}"
    )
    assert scripted[scripted.index("--serve.fallback_local") + 1] == "true", (
        "the serve-tier fleet arms the local fallback (experience never stops)"
    )
    assert float(scripted[scripted.index("--serve.fallback_after_s") + 1]) > 0


def test_league_service_manifest():
    """League service (ISSUE 17): a single-replica Deployment + Service
    (the registry dir is the state; restart = matches.jsonl replay, not
    loss); port agreement end to end
    (league.port == containerPort == probe port == Service port ==
    every client's --serve.league / --serve.league_endpoint); the slot
    count must equal the inference tier's --serve.models minus one
    (slot 0 is the live tree — drift strands assignments or leaves
    slots the sync can never fill); and the serve tier must actually
    run multi-model with the sync pointed back at this Service. That the
    committed --league.policy PARSES is graftproto's SVC003 gate now —
    the real parse_match_policy runs on this literal in the lint."""
    (_, dep), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "league" and d["kind"] == "Deployment"
    ]
    assert dep["spec"]["replicas"] == 1, "one pod owns the population"
    c = dep["spec"]["template"]["spec"]["containers"][0]
    assert c["command"][2] == "dotaclient_tpu.league.server"
    args = c["args"]

    assert args[args.index("--league.policy") + 1].strip(), (
        "shipped matchmaking policy must be non-empty (SVC003 proves it "
        "parses; an empty value would silently skip the lint's proof)"
    )

    lport = int(args[args.index("--league.port") + 1])
    assert {p["containerPort"] for p in c["ports"]} == {lport}
    assert c["readinessProbe"]["httpGet"]["port"] == lport
    assert c["livenessProbe"]["httpGet"]["port"] == lport
    (_, svc), = [
        (f, d) for f, d in DOCS
        if d["kind"] == "Service" and d["metadata"]["name"] == "league"
    ]
    assert {p["port"] for p in svc["spec"]["ports"]} == {lport}

    assert args[args.index("--league.dir") + 1], (
        "a standing league without a registry dir forgets its population "
        "on every restart"
    )

    # cross-tier wiring: slots == serve models - 1, sync closed-loop
    (_, sts), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "inference" and d["kind"] == "StatefulSet"
    ]
    sargs = sts["spec"]["template"]["spec"]["containers"][0]["args"]
    models = int(sargs[sargs.index("--serve.models") + 1])
    assert models > 1, "the league tier needs a multi-model serve tier"
    slots = int(args[args.index("--league.slots") + 1])
    assert slots == models - 1, (
        f"league slots {slots} must equal serve models {models} - 1 "
        "(slot 0 stays the live fan-out tree)"
    )
    assert sargs[sargs.index("--serve.league_endpoint") + 1] == f"league:{lport}", (
        "the serve tier's assignment sync must dial this league Service"
    )
    serve_ep = args[args.index("--league.serve_endpoint") + 1]
    sport = sargs[sargs.index("--serve.port") + 1]
    assert serve_ep.endswith(f":{sport}"), (
        "/match hands fleets the serve tier's port"
    )
    # the league fleet's --serve.league must dial this same Service:port
    for fname, ac in _our_containers():
        if ac.get("command") and ac["command"][2] == "dotaclient_tpu.runtime.actor":
            a = ac.get("args", [])
            if a[a.index("--opponent") + 1] == "league" and "--serve.league" in a:
                assert a[a.index("--serve.league") + 1] == f"league:{lport}", (
                    f"{fname}: league fleet dials a different league port"
                )


def test_session_continuity_manifests():
    """Session continuity (PR 13), gated on a green SERVE_HANDOFF_SOAK
    verdict (the WIRE_SOAK flip pattern): the carry-store Deployment +
    Service exist, every inference replica streams to it
    (--serve.handoff_endpoint naming the Service and its port), and the
    scripted serve-tier fleet arms resume + load routing with a resume
    window under the fallback budget (a starved fallback decision would
    idle the fleet)."""
    import json

    verdict = json.loads((K8S.parent / "SERVE_HANDOFF_SOAK.json").read_text())["verdict"]
    bad = [k for k, v in verdict.items() if isinstance(v, bool) and not v]
    assert not bad, f"handoff opt-in requires a green SERVE_HANDOFF_SOAK verdict: {bad}"

    (_, store), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "carry-store" and d["kind"] == "Deployment"
    ]
    sc = store["spec"]["template"]["spec"]["containers"][0]
    assert sc["command"][2] == "dotaclient_tpu.serve.handoff"
    sargs = sc["args"]
    store_port = int(sargs[sargs.index("--port") + 1])
    assert int(sargs[sargs.index("--keep") + 1]) >= 2, (
        "keep>=2 is load-bearing: the previous boundary covers lost-ack resumes"
    )
    (_, ssvc), = [
        (f, d) for f, d in DOCS
        if d["kind"] == "Service" and d["metadata"]["name"] == "carry-store"
    ]
    assert store_port in {p["port"] for p in ssvc["spec"]["ports"]}

    (_, sts), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "inference" and d["kind"] == "StatefulSet"
    ]
    sts_args = sts["spec"]["template"]["spec"]["containers"][0]["args"]
    assert sts_args[sts_args.index("--serve.handoff_endpoint") + 1] == (
        f"carry-store:{store_port}"
    ), "inference replicas must stream boundaries to the carry-store Service"

    for fname, c in _our_containers():
        if c.get("command") and c["command"][2] == "dotaclient_tpu.runtime.actor":
            a = c.get("args", [])
            if a[a.index("--opponent") + 1] != "scripted_hard":
                continue
            assert a[a.index("--serve.resume") + 1] == "true", (
                f"{fname}: the serve-tier fleet rides session continuity"
            )
            assert a[a.index("--serve.route") + 1] == "load"
            window = float(a[a.index("--serve.resume_window_s") + 1])
            budget = float(a[a.index("--serve.fallback_after_s") + 1])
            assert 0 < window < budget, (
                "resume window must sit under the fallback budget, or the "
                "fallback decision starves behind resume retries"
            )


def test_control_plane_manifest():
    """Control plane (PR 16): a single-replica Deployment + Service;
    the driver ships "static" (observe-only until
    the ledger earns the k8s flip), every port agrees (control.port ==
    containerPort == probe port == Service port — clients dial
    control:control-plane:<that port>), and the scrape flag lists name
    one per-pod DNS endpoint per broker/inference replica (list drift =
    a blind or phantom scrape, exactly the serve endpoint-list rule)."""
    from dotaclient_tpu.control.policy import parse_policy

    (_, dep), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "control-plane" and d["kind"] == "Deployment"
    ]
    assert dep["spec"]["replicas"] == 1, "the controller is a decision loop, not a data path"
    c = dep["spec"]["template"]["spec"]["containers"][0]
    assert c["command"][2] == "dotaclient_tpu.control.server"
    args = c["args"]

    # that the clause string PARSES (and that every meter it keys on is
    # registered and actually exported by the scraped tier) is
    # graftproto's SVC002/SVC003 gate; the checks below are the SEMANTIC
    # shipping pins a parser can't know — sane bands, observe-only
    # driver, poll cadence under every cooldown
    clauses = parse_policy(args[args.index("--control.policy") + 1])
    for cl in clauses:
        assert cl.min >= 1 and cl.low < cl.high and cl.cooldown_s > 0
    assert {cl.tier for cl in clauses} >= {"server", "broker"}

    assert args[args.index("--control.driver") + 1] == "static", (
        "ship observe-only first; the k8s flip is a flag change with a "
        "ledger behind it, not part of this rollout"
    )

    cport = int(args[args.index("--control.port") + 1])
    assert {p["containerPort"] for p in c["ports"]} == {cport}
    assert c["readinessProbe"]["httpGet"]["port"] == cport
    assert c["livenessProbe"]["httpGet"]["port"] == cport
    (_, svc), = [
        (f, d) for f, d in DOCS
        if d["kind"] == "Service" and d["metadata"]["name"] == "control-plane"
    ]
    assert {p["port"] for p in svc["spec"]["ports"]} == {cport}

    poll_s = float(args[args.index("--control.poll_s") + 1])
    assert all(poll_s < cl.cooldown_s for cl in clauses), (
        "poll cadence must sit well under every cooldown: the poll "
        "samples meters, the cooldown waits for the fleet to respond"
    )

    # scrape lists cross-checked against the committed replica counts
    (_, inf), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "inference" and d["kind"] == "StatefulSet"
    ]
    servers = args[args.index("--control.servers") + 1].split(",")
    assert servers == [
        f"inference-{i}.inference:9100" for i in range(inf["spec"]["replicas"])
    ], "server scrape list must name every inference replica exactly"
    (_, brk), = [
        (f, d) for f, d in DOCS
        if d["metadata"]["name"] == "broker" and d["kind"] == "StatefulSet"
    ]
    brokers = args[args.index("--control.brokers") + 1].split(",")
    assert brokers == [
        f"broker-{i}.broker:9100" for i in range(brk["spec"]["replicas"])
    ], "broker scrape list must name every broker shard exactly"


def test_graftproto_covers_probes_and_grammars():
    """The hand-pinned probe-path and policy-parses checks that used to
    live in this suite are now the SVC001/SVC003 lint gate (graftproto).
    This test pins the COVERAGE, not the verdict: every manifest probe
    path is extracted and attributed to its binary, every committed
    policy/alert/matchmaking clause reaches the grammar proof, and each
    probe path re-verifies against the binary's actual served surface —
    so the lint's clean verdict genuinely spans the surfaces this suite
    stopped pinning by hand."""
    import os

    from dotaclient_tpu.analysis.core import RepoContext, parse_modules
    from dotaclient_tpu.analysis.fleetgraph import fleet_graph

    root = str(K8S.parent)
    ctx = RepoContext(
        root=root,
        modules=parse_modules(root, [os.path.join(root, "dotaclient_tpu")]),
        k8s_dir=str(K8S),
        scripts_dir=os.path.join(root, "scripts"),
        registry_path=os.path.join(root, "dotaclient_tpu", "obs", "registry.py"),
        config_path=os.path.join(root, "dotaclient_tpu", "config.py"),
    )
    g = fleet_graph(ctx)

    probes = {(p.relpath, p.route, p.binary) for p in g.probe_routes()}
    assert ("k8s/inference.yaml", "/healthz", "dotaclient_tpu.serve.server") in probes
    assert ("k8s/league.yaml", "/healthz", "dotaclient_tpu.league.server") in probes
    assert ("k8s/control.yaml", "/healthz", "dotaclient_tpu.control.server") in probes
    assert ("k8s/fleetd.yaml", "/healthz", "dotaclient_tpu.obs.fleetd") in probes
    # the block-style learner probes and the prometheus scrape
    # annotations are edges too, not just the flow-style one-liners
    assert ("k8s/learner.yaml", "/healthz", "dotaclient_tpu.runtime.learner") in probes
    assert ("k8s/learner.yaml", "/metrics", "dotaclient_tpu.runtime.learner") in probes

    # SVC001 restated: every extracted probe path is genuinely served
    for p in g.probe_routes():
        served = g.served_by(p.binary)
        assert not served or p.route in served, (
            f"{p.relpath}:{p.line}: probe {p.route!r} not served by {p.binary}"
        )

    grammars = {(lit.relpath, lit.grammar) for lit in g.grammar_literals()}
    assert ("k8s/control.yaml", "control_policy") in grammars
    assert ("k8s/league.yaml", "league_policy") in grammars
    assert ("k8s/fleetd.yaml", "fleet_alerts") in grammars


def test_actor_fleet_scale_and_kill_switch():
    (_, doc), = [(f, d) for f, d in DOCS if d["metadata"]["name"] == "actors"]
    assert doc["spec"]["replicas"] >= 2
    actor = [c for c in doc["spec"]["template"]["spec"]["containers"] if c["name"] == "actor"][0]
    args = actor["args"]
    assert "--max_weight_age_s" in args, "actors must carry the stale-weights kill switch"


def test_kubectl_dry_run_if_available():
    import shutil

    if shutil.which("kubectl") is None:
        pytest.skip("kubectl not in image; structural checks above stand in")
    for path in MANIFESTS:
        proc = subprocess.run(
            ["kubectl", "apply", "--dry-run=client", "-f", str(path)],
            capture_output=True,
            text=True,
        )
        assert proc.returncode == 0, f"{path.name}: {proc.stderr}"


def test_learner_pack_workers_sized_to_cpu_request():
    """Parallel host feed (PR 11): every learner manifest ships
    --staging.pack_workers sized by the README rule — one packer worker
    per 4 cpu-request cores, capped at 4 (pack is copy-bound; workers
    past the memory-bandwidth knee only add contention). A manifest that
    raises the cpu request without re-deriving the worker count, or
    ships workers with no cpu basis, fails here."""
    for name in ("learner", "learner-multihost"):
        (_, doc), = [
            (f, d) for f, d in DOCS
            if d["metadata"]["name"] == name and d["kind"] != "Service"
        ]
        c = doc["spec"]["template"]["spec"]["containers"][0]
        args = c["args"]
        assert "--staging.pack_workers" in args, f"{name}: parallel feed not sized"
        workers = int(args[args.index("--staging.pack_workers") + 1])
        cpu_req = c["resources"]["requests"]["cpu"]
        cores = float(cpu_req.rstrip("m")) / (1000.0 if cpu_req.endswith("m") else 1.0)
        expect = max(1, min(4, int(cores // 4)))
        assert workers == expect, (
            f"{name}: pack_workers {workers} != sizing rule min(4, cpu_request//4) "
            f"= {expect} for cpu request {cpu_req}"
        )
