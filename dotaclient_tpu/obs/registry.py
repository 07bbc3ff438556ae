"""Metric-name registry: the documented contract between emitters and
dashboards.

Dashboards and alerts select series BY NAME; a rename in learner.py (or
a new scalar nobody documents) silently drops/misses series with no
error anywhere. This registry is the single source of truth for every
scalar the learner/staging/replay/obs pipeline emits, and
tests/test_obs.py::test_emitted_scalars_are_registered drives a real
closed-loop learner and fails tier-1 if an emitted name isn't here —
so a rename must touch this file (and therefore the dashboards note in
README) to land.

Two name classes:
- SCALARS: exact, hand-documented names.
- PREFIXES: documented dynamic families whose tails are data-dependent
  (histogram bucket edges, replay reservoir stats, checkpoint-mirror
  stats, per-stage trace scalars). A family prefix documents the whole
  family; keep these FEW and specific — a catch-all prefix would defeat
  the drift guard.

This contract is enforced THREE ways, and every guard parses THIS file:
- runtime: the tier-1 drift guard above catches any name a real learner
  window emits that isn't registered;
- lint time: graftlint's OBS001 (dotaclient_tpu/analysis/obs_rules.py)
  AST-checks every STRING-LITERAL scalar name passed to
  MetricsLogger.log against SCALARS/PREFIXES before the code ever runs,
  and checks each f-string key by its constant head against the PREFIXES
  families (it reads the two dicts below by AST, never by import — keep
  them literal dicts of constant string keys). Fully-dynamic keys
  (loop-forwarded stats) are the runtime guard's half of the contract;
- fleet lint: graftproto (dotaclient_tpu/analysis/proto_rules.py)
  resolves every meter the SHIPPED k8s autoscaler/alert clauses name
  (SVC002) and every conservation-LEDGERS term (SVC004) against this
  registry AND against what the scraped tier's import closure actually
  emits — so a name here that no tier exports, or a clause naming an
  unregistered meter, fails lint before any pod boots.
"""

from __future__ import annotations

from typing import Dict

# Exact scalar names → one-line meaning. Grouped by emitter.
SCALARS: Dict[str, str] = {
    # --- compiled train step (parallel/train_step.py metric_keys) ------
    "loss": "total PPO objective",
    "policy_loss": "clipped-surrogate policy term",
    "value_loss": "clipped value regression term",
    "entropy": "mean policy entropy over real steps",
    "ratio_mean": "mean importance ratio",
    "ratio_clip_frac": "fraction of ratios clipped",
    "approx_kl": "approximate KL(new || behavior)",
    "advantage_mean": "mean GAE advantage (pre-normalization)",
    "return_mean": "mean bootstrapped return target",
    "value_mean": "mean predicted value",
    "replay_trunc_frac": "fraction of replayed rows with truncated IS ratio",
    "grad_norm": "global gradient norm before clipping",
    "aux_loss": "auxiliary value-head loss (aux_heads only)",
    "ppo_updates_done": "minibatch updates applied (KL early stop aware)",
    "ppo_kl_stopped": "1 if the KL early stop fired for this batch",
    # --- learner loop (runtime/learner.py) -----------------------------
    "env_steps_per_sec": "real (unmasked) env steps trained per second",
    "time_wait_batch_s": (
        "per-step host wait for a packed batch (pipelined loop: paid on "
        "the prefetch lane, hidden behind the device step)"
    ),
    "time_device_put_s": (
        "per-step host→device transfer time (pipelined loop: paid on "
        "the prefetch lane)"
    ),
    "time_step_s": (
        "per-step residual — device step + dispatch (pipelined loop: "
        "wall minus the exposed take-wait)"
    ),
    "active_actors": "actors heard from within the heartbeat window",
    "staleness_dropped": "rollouts dropped for version staleness (cumulative)",
    "staging_quarantined": (
        "frames filed in the staging dead-letter ring (parse/layout "
        "poison — evidence kept, dumped by the flight recorder)"
    ),
    "queue_ready": "packed batches waiting in the staging queue",
    "episodes": "episodes completed (cumulative, from done frames)",
    # --- experience wire (transport/serialize.py DTR3, staged by
    #     runtime/staging.py, emitted by the learner loop) --------------
    "wire_bytes_consumed_total": (
        "serialized experience bytes entering the staging intake "
        "(cumulative; the bf16 wire roughly halves the obs share)"
    ),
    "wire_frames_obs_bf16_total": (
        "frames whose float obs leaves traveled as bf16 (DTR3 quantized "
        "wire, --wire.obs_dtype bf16 producers)"
    ),
    "wire_frames_obs_f32_total": (
        "frames whose float obs leaves traveled as f32 (legacy DTR1/DTR2 "
        "producers) — nonzero during a rolling upgrade"
    ),
    "weights_published": "weight fanout frames actually sent",
    "weights_coalesced": "weight publishes superseded before sending",
    "weights_publish_failed": (
        "weight publishes that raised on the publisher thread (the broker "
        "refused the frame); logged and counted, the next one is tried"
    ),
    "loop_dispatch_gap_max_s": (
        "longest interval between two consecutive train-step dispatches "
        "in the metrics window, less the time blocked in a metrics sync "
        "inside it (pipelined loop): how long a publish, or anything else "
        "on the host, kept the loop from handing the device its next step"
    ),
    "loop_inflight_max": (
        "most steps a dispatch of the metrics window found ahead of it, "
        "dispatched and not yet complete (the loop polls is_ready() on the "
        "results it holds; at most metrics_every - 1, since a sync empties "
        "the queue)"
    ),
    "loop_inflight_mean": "mean of the same count over the window's dispatches",
    # --- compiles (obs/spans.py, a jax.monitoring listener the learner
    #     registers once; process-wide, cumulative) ---------------------
    "compile_count_total": (
        "programs this process compiled or loaded from the persistent "
        "cache (MUST stay flat in steady state)"
    ),
    "compile_s_total": (
        "seconds spent tracing, lowering and compiling or loading them "
        "(at the first metrics window: what set-up spent on its programs)"
    ),
    "mean_episode_return": "mean per-episode return over consumed frames",
    # --- evaluator (eval/evaluator.py) ---------------------------------
    "win_rate": "evaluation win rate vs the scripted yardstick",
    "mean_eval_return": "mean evaluation episode return",
    "trueskill_mu": "anchored TrueSkill mean",
    "trueskill_sigma": "anchored TrueSkill uncertainty",
    "skill": "conservative TrueSkill estimate (mu - 3 sigma)",
    # --- obs (dotaclient_tpu/obs/trace.py) -----------------------------
    "trace_e2e_actor_apply_s": "mean actor-publish → train-step-apply latency",
    # --- obs compute (dotaclient_tpu/obs/compute.py) -------------------
    "compute_phase_fetch_s": "mean per-step host wait for a packed batch",
    "compute_phase_pack_s": "mean per-step io.pack fallback time (≈0 on the fused path)",
    "compute_phase_h2d_s": "mean per-step fenced host→device transfer time",
    "compute_phase_device_step_s": "mean per-step fenced device train-step time",
    "compute_phase_host_s": "mean per-step publish/checkpoint/metrics host work",
    "compute_phase_wall_s": "mean loop-iteration wall time (phases sum to ≈ this)",
    "compute_phase_fetch_frac": "fetch share of step wall (watchdog starvation signal)",
    "compute_recompiles_total": "train-step signatures beyond the first (MUST stay 0 steady-state)",
    "compute_compiles_total": "train-step compiles including the first",
    "compute_compile_s": "cumulative train-step compile wall seconds",
    "compute_last_compile_s": "wall seconds of the most recent compile",
    "compute_flops_per_sec": "achieved model FLOP/s (ops/flops.py analytic count)",
    "compute_mfu": "cumulative model-FLOPs utilization vs platform peak (TPU only)",
    # --- vector actor fleet (runtime/actor.py InferenceBatcher) --------
    # Emitted by InferenceBatcher.stats() / VectorActor.stats():
    # bench_actors.py commits them into ACTOR_FLEET.json, and a
    # metrics-serving actor exports them as scrape gauges. The inference
    # service (dotaclient_tpu/serve/) runs the SAME batcher and exports
    # the same family on its own /metrics — deliberately shared names,
    # so fleet and serve dashboards read one distribution.
    "actor_offered_steps_per_sec": "real env steps offered by this process per second",
    "actor_batch_occupancy": "mean real-rows / capacity of the batched inference tick",
    "actor_gather_wait_s": "mean per-tick wait assembling the batch (bounded by --gather_window_s)",
    "actor_jit_step_s": "mean per-tick batched jit inference latency (incl. the one device_get)",
    # Producer conservation ledger (VectorActor.stats; obs/fleet.py
    # audits attempted = published + shed + failed live):
    "actor_publish_attempted_total": (
        "rollout chunks this process tried to publish (published + shed "
        "+ failed, derived from the same reads so the identity is exact)"
    ),
    "actor_rollouts_published_total": "rollout chunks acked by the broker (cumulative)",
    # --- inference service (dotaclient_tpu/serve/server.py) ------------
    "serve_requests_total": "policy-step requests handled (cumulative, all connections)",
    "serve_unknown_client_total": (
        "steps naming a client_key with no resident carry and no "
        "episode-start flag (server restarted/evicted; the client "
        "abandons the episode)"
    ),
    "serve_bad_requests_total": "malformed step requests refused",
    "serve_episode_resets_total": "carry resets on EPISODE_START flags (cumulative)",
    "serve_evictions_total": "carries evicted on client disconnect (cumulative)",
    "serve_weight_swaps_total": "param-tree hot-swaps applied between ticks (cumulative)",
    "serve_version": "model version of the currently-serving param tree",
    "serve_clients_connected": "live client connections",
    "serve_carries_resident": "LSTM carries held server-side across all connections",
    # --- serve placement load (serve/server.py load(), the S_INFO
    #     "load" dict as scrape gauges — what the control plane's
    #     policy loop and load-aware routing read) ----------------------
    "serve_load_clients": "live client connections (the S_INFO load report's clients field)",
    "serve_load_occupancy": "mean real-rows / capacity over the tick-occupancy histogram",
    "serve_load_pending": "step requests queued for the next inference tick",
    "serve_load_capacity": "batched-tick capacity (--serve.max_batch)",
    # --- session continuity, SERVER side (serve/server.py +
    #     serve/handoff.py; zero with --serve.handoff_endpoint unset) --
    "serve_handoff_store_writes_total": (
        "chunk-boundary carries write-ahead-streamed to the shared "
        "store BEFORE the chunk-fill reply (cumulative)"
    ),
    "serve_handoff_store_errors_total": (
        "carry-store RPCs that failed (write or failover read); the "
        "affected sessions degrade to PR-10 abandon-on-failover"
    ),
    "serve_handoff_resumes_total": (
        "sessions restored from the store on failover (S_RESUME "
        "answered OK; the client replays and the episode continues)"
    ),
    "serve_handoff_resume_misses_total": (
        "resume handshakes refused (no store, store miss, or no entry "
        "matching the client's boundary) — the client abandons"
    ),
    "serve_handoff_replayed_steps_total": (
        "FLAG_REPLAY steps served — buffered partial-chunk observations "
        "re-driven to rebuild a resumed session's mid-chunk carry"
    ),
    # --- serve-tier resilience, CLIENT side (serve/client.py
    #     RemoteFleet.stats; scrape-only like actor_*) ------------------
    "serve_failover_endpoints": "configured inference endpoints in the failover list",
    "serve_failover_endpoints_down": "endpoints currently sitting out a health cooldown",
    "serve_failover_total": "failovers to a different endpoint (cumulative)",
    "serve_failover_reconnects_total": "reconnect dials attempted (cumulative)",
    "serve_failover_episodes_abandoned_total": (
        "episodes abandoned on remote-inference failure — connection "
        "loss, reply deadline, UNKNOWN_CLIENT (the serve chaos soak's "
        "explicit abandon ledger)"
    ),
    # --- session continuity + routing tier, CLIENT side
    #     (serve/client.py RemoteFleet.stats; scrape-only) -------------
    "serve_handoff_client_resumes_total": (
        "episodes RESUMED after a remote-inference failure instead of "
        "abandoned (--serve.resume; the zero-abandon soak's ledger)"
    ),
    "serve_handoff_replay_steps_total": (
        "replay steps sent while rebuilding resumed sessions (at most "
        "one chunk per resume — the recompute bound)"
    ),
    "serve_route_load_mode": "1 when --serve.route load is active (0 = PR-10 list order)",
    "serve_route_probes_total": (
        "endpoint load probes issued at (re)connect time (S_INFO dials "
        "across the in-rotation candidates)"
    ),
    "serve_route_picks_total": "connects whose endpoint order came from a load probe pass",
    "serve_topology_refreshes_total": (
        "endpoint lists adopted from the control plane's GET /topology "
        "(--serve.endpoint control:<host:port>; 0 with literal lists)"
    ),
    "serve_topology_errors_total": (
        "failed /topology fetches — the client keeps its current list "
        "(rollback semantics: discovery can only improve on the static list)"
    ),
    "serve_fallback_engaged": "1 while the local-policy fallback is stepping episodes",
    "serve_fallback_engagements_total": (
        "distinct fallback engagements — counted per outage, not per "
        "return-to-remote probe cycle"
    ),
    "serve_fallback_steps_total": "policy steps served by the warm local tree (cumulative)",
    "serve_fallback_version": "model version of the broker-fanout-refreshed local tree",
    # --- multi-model serve tier (serve/server.py, --serve.models > 1) --
    "serve_models_resident": "param-tree slots resident on this server (--serve.models)",
    "serve_league_syncs_total": (
        "league-assignment slot installs applied by the sync loop "
        "(--serve.league_endpoint; cumulative)"
    ),
    "serve_league_sync_errors_total": (
        "failed league assignment/snapshot polls — current slots keep serving"
    ),
    # --- full-state checkpointing (runtime/checkpoint.py aux manifests,
    #     runtime/learner.py CheckpointWorker) — emitted only when
    #     --ckpt.full_state / --ckpt.async_save are on -----------------
    "ckpt_aux_written": "full-state aux manifests written (cumulative)",
    "ckpt_aux_superseded": "aux manifests coalesced away before writing (latest-wins)",
    "ckpt_aux_failures": "aux manifest writes that failed (prior step stays restorable)",
    "ckpt_last_aux_bytes": "size of the newest aux manifest (reservoir + pending + RNG)",
    "ckpt_last_aux_step": "step label of the newest durable aux manifest",
    "ckpt_async_saves_total": "checkpoints written by the off-critical-path saver",
    "ckpt_async_coalesced_total": "async checkpoints superseded before writing",
    # --- resume provenance (runtime/learner.py _restore_full_state):
    #     merged into the FIRST metrics window after a restore ----------
    "resume_restored_step": "checkpoint step label this boot restored (-1 = none)",
    "resume_version_hwm_bump": (
        "versions the counter jumped past the restored step to the "
        "published high-water mark (staleness stamps stay monotonic)"
    ),
    "resume_reservoir_entries": "replay-reservoir entries rehydrated from the aux manifest",
    "resume_pending_frames": "staged-but-untrained frames re-injected from the aux manifest",
    "resume_restore_wall_s": "wall seconds from restore start to full-state rehydration",
    # --- obs watchdog (dotaclient_tpu/obs/watchdog.py) -----------------
    "watchdog_ok": "1 while /healthz serves 200, 0 once tripped",
    "watchdog_strikes": (
        "escalation ladder position: max of consecutive failing checks "
        "(stall/NaN) and consecutive failing metrics windows "
        "(starvation/regression) — window strikes advance per logged "
        "window, not per check"
    ),
    "watchdog_trips_total": "times the watchdog flipped /healthz to 503",
    "watchdog_checks_total": "watchdog checks executed",
}

# Documented dynamic families (prefix → meaning of the family).
PREFIXES: Dict[str, str] = {
    # program spans (obs/spans.py `span(name, **ids)`, emitted by the
    # learner loop with every metrics window): span_<name>_s_total and
    # span_<name>_n_total (cumulative seconds and count), the name's `.`
    # written `_`. Names: loop.dispatch / .publish_submit / .sync /
    # .sync_ready / .sync_get / .checkpoint; lane.retire / .handoff;
    # staging.pop / .ingest / .pack / .ready_wait; publish.d2h /
    # .ready_wait / .copy / .serialize / .send / .latency / .age;
    # setup.learner_init / .init_params / .restore / .publish0. The same
    # spans lie on the profiler's timeline while a session is open (there
    # also loop.take, lane.wait_batch and lane.device_put, whose sums are
    # pipeline_device_idle_s, time_wait_batch_s and time_device_put_s). A
    # family: one span more is one name more.
    "span_": "program spans: cumulative seconds and count (obs/spans.py)",
    # dispatches that found no step in flight (runtime/learner.py
    # _InFlight, emitted with every metrics window, cumulative):
    # loop_starved_n_total counts them (a run's first is none), and
    # loop_starved_<cause>_s_total takes each one's seconds since the
    # loop last knew the device busy (its previous dispatch) or done
    # (loop.sync_ready's exit), under what the loop spent most of them
    # in: take (the lane had no batch), sync (the metrics read and the
    # window's bookkeeping), publish (loop.publish_submit), checkpoint,
    # other. Exact after a sync, an upper bound after a dispatch.
    "loop_starved_": "dispatches that found the device's queue empty: count, and seconds by cause",
    # replay reservoir stats + age histogram, re-prefixed by staging:
    # replay_occupancy, replay_admitted, replay_age_le_<edge>, ...
    "replay_": "replay reservoir health (runtime/staging.py stats passthrough)",
    # checkpoint remote-mirror health: ckpt_mirror_lag_steps, ...
    "ckpt_mirror_": "checkpoint remote-mirror health (runtime/checkpoint.py)",
    # per-stage pipeline latency histograms + means:
    # trace_<stage>_ms_le_<edge>, trace_<stage>_ms_gt_<last>,
    # trace_<stage>_mean_ms (obs/trace.py STAGES)
    "trace_": "pipeline per-stage latency scalars (obs/trace.py)",
    # obs gauges exported only on the scrape surface (not JSONL):
    # obs_broker_experience_depth, obs_staging_*, ...
    "obs_": "live scrape-surface gauges (obs/__init__.py sources)",
    # rows-per-fired-tick occupancy histogram (InferenceBatcher):
    # actor_tick_rows_<k> = cumulative ticks whose batch carried exactly
    # k real rows, k in 1..capacity (k=0 cannot fire — a tick starts
    # from its first request). The capacity-dependent tail is why this
    # is a family, not exact names; the mean lives in
    # actor_batch_occupancy. Exported by vector actors AND the
    # inference service (same batcher, same distribution semantics).
    "actor_tick_rows_": "rows-per-fired-tick occupancy histogram (runtime/actor.py InferenceBatcher)",
    # learner loop's lane accounting (runtime/learner.py PrefetchLane +
    # obs/compute.py StepPhaseTimer):
    # pipeline_prefetch_s (prefetch-lane busy seconds per step:
    # fetch+pack+h2d, hidden behind the device step),
    # pipeline_prefetch_fetch_s / _pack_s / _h2d_s (the lane's own phase
    # split, fenced ON THE LANE so attribution costs no overlap),
    # pipeline_device_idle_s (the loop's exposed wait for a prefetched
    # batch; no bound on the device's idle time while steps are queued:
    # loop_starved_take_s_total counts the part that found none),
    # pipeline_overlap_ratio (share of lane work hidden behind the
    # device step; 1.0 = the host fully disappeared). A family: the
    # lane split can grow phases.
    "pipeline_": "overlapped learner pipeline lane accounting (runtime/learner.py)",
    # parallel host feed scoreboard (runtime/staging.py _PackPool +
    # parallel/fused_io.py TransferRing, emitted by the learner loop
    # only when --staging.pack_workers > 1):
    # staging_pack_workers, staging_pack_worker_busy_s_<i>,
    # staging_pack_worker_stall_s_<i> (per-worker seconds executing /
    # idle — the worker-count sizing signal), staging_pack_ring_depth,
    # staging_pack_ring_occupancy (slots packing/ready/in-transfer),
    # staging_pack_ring_wait_s (assembler blocked on a free slot —
    # nonzero means H2D/device, not pack, is the longest stage),
    # staging_pack_wall_s, staging_pack_rows_per_s (packer-proper rate).
    # The per-worker tail is why this is a family, not exact names.
    "staging_pack_": "parallel host feed scoreboard (sharded pack pool + transfer ring)",
    # broker-fabric fan-in consumer (transport/fabric.py FabricBroker,
    # emitted by the learner loop only when --broker_url is a shard
    # list): fanin_queue_depth, fanin_delivered_total,
    # fanin_fence_dropped_total (epoch-stale deliveries dropped — the
    # stale-shard-resurrection proof counter), fanin_dup_dropped_total,
    # fanin_pop_threads, fanin_keys_tracked,
    # fanin_publish_failovers_total, fanin_publish_failed_total.
    "fanin_": "broker-fabric fan-in consumer ledgers (transport/fabric.py)",
    # per-shard fabric meters, TWO emitters: the learner-side fan-in
    # consumer exports broker_shard_<i>_popped_total,
    # broker_shard_<i>_starved_s (pop thread idle/backing off against
    # shard i — a starving shard index is the page), broker_shard_<i>_up
    # (tail = the consumer's shard-list index); the shard BINARY's own
    # --metrics_port surface exports the un-indexed ledger gauges
    # broker_shard_enqueued_total/_popped_total/_dropped_total/
    # _shed_total/_reply_lost_total/_evicted_low_total/_resident/_depth
    # (transport/fabric.py shard_metrics_source — the fleet auditor's
    # shard-ledger terms).
    "broker_shard_": "per-shard broker-fabric health (transport/fabric.py)",
    # broker admission control + actor publish degradation:
    # broker_shed_observed_total, broker_shed_publish_failed_total,
    # broker_shed_throttle_s (runtime/actor.py ShedThrottle /
    # VectorActor.stats; transport/tcp.py watermarks are the source)
    "broker_shed_": "broker load-shed observability (admission refusals + actor throttle)",
    # in-network batch assembly tier (--broker.assemble; transport/tcp.py
    # BrokerServer.assemble_ledger via transport/fabric.py
    # shard_metrics_source — the shard binary's --metrics_port surface):
    # broker_assemble_rows_admitted_total / _rows_packed_total /
    # _rows_reject_total (frames the classic ingest would also
    # dead-letter) / _rows_bypassed_total (classic CONSUME popped them
    # wire-form while armed) / _rows_dropped_total (drop-oldest +
    # priority eviction) / _rows_resident (assembled-but-unpopped rows,
    # the conservation gauge) / _blocks_built_total / _blocks_served_total
    # / _block_bytes_total / _cpu_s_total (shard-side pack seconds — the
    # CPU the learner host no longer spends). The assembled-rows
    # conservation identity over these terms is a fleet LEDGER
    # (obs/fleet.py) audited by graftproto SVC004 and fleetd.
    "broker_assemble_": "in-network batch assembly ledger (transport/tcp.py assemble tier)",
    # per-configured-endpoint health gauges (serve/client.py
    # RemoteFleet.stats): serve_endpoint_up_<i> (1 = in rotation, 0 =
    # sitting out a cooldown) and serve_endpoint_cooldown_s_<i>
    # (remaining cooldown seconds), i = index into --serve.endpoint.
    # PR 10 tracked health internally; these make WHICH replica a fleet
    # marked down operator-visible. A family because the tail is the
    # endpoint-list index.
    "serve_endpoint_": "per-endpoint client-side health gauges (serve/client.py)",
    # carry-store service gauges (serve/handoff.py CarryStoreServer
    # /metrics): serve_handoff_store_sessions, _puts_total, _gets_total,
    # _hits_total, _misses_total, _stale_total, _requests_total,
    # _bad_requests_total — the store binary's own scrape surface.
    "serve_handoff_store_": "carry-store service health (serve/handoff.py)",
    # seeded fault-injection meters (dotaclient_tpu/chaos/ ChaosBroker):
    # chaos_ops, chaos_corrupted, chaos_truncated, chaos_duplicated,
    # chaos_resets, chaos_sheds, chaos_stall_s, chaos_latency_s —
    # emitted only when --chaos.enabled (never in production)
    "chaos_": "fault-injection layer meters (dotaclient_tpu/chaos/)",
    # control-plane loop health (dotaclient_tpu/control/server.py
    # ControlPlane.stats, served on the controller's own surface):
    # control_polls_total, control_scrapes_total,
    # control_scrape_errors_total, control_scale_ups_total,
    # control_scale_downs_total, control_holds_total,
    # control_actuation_failures_total, control_topology_epoch,
    # control_managed_tiers, control_decisions_ledgered,
    # control_policy_clauses, control_replicas_<tier>. A family because
    # the per-tier tail is data-dependent (the managed-tier set).
    "control_": "control-plane autoscaler loop health (dotaclient_tpu/control/)",
    # per-model-slot serve ledgers (serve/server.py InferenceServer.stats,
    # emitted only at --serve.models > 1): serve_model_requests_total_<m>,
    # serve_model_swaps_total_<m>, serve_model_evictions_total_<m>,
    # serve_model_version_<m>, m = model slot index. A family because the
    # tail is the slot index.
    "serve_model_": "per-model-slot serve tier ledgers (serve/server.py)",
    # league population health (eval/league.py League.stats per-actor
    # pools AND dotaclient_tpu/league/ LeagueService.stats, the standing
    # service): league_pool_size, league_snapshots_total,
    # league_evictions_total, league_opponent_samples_total,
    # league_results_total, league_candidates, league_slots_assigned,
    # league_promotions_total, league_matches_total,
    # league_match_empty_total, league_bad_results_total,
    # league_fanout_snapshots_total, league_fanout_errors_total.
    "league_": "league population health (eval/league.py + dotaclient_tpu/league/)",
    # fleet telemetry plane (dotaclient_tpu/obs/fleet.py FleetAggregator,
    # served by obs/fleetd): fleet_targets(_up), fleet_polls_total,
    # fleet_scrape_errors_total, fleet_fences_total,
    # fleet_unaccounted_frames / fleet_overaccounted_frames /
    # fleet_fenced_frames (the conservation-audit headline),
    # fleet_ledger_<name>_* per ledger identity, fleet_tier_up_<tier>,
    # fleet_e2e_env_steps_per_sec vs fleet_device_only_env_steps_per_sec
    # and fleet_host_wall_gap (the committed 40x scoreboard, live),
    # fleet_staleness_e2e_s_*, fleet_trace_<stage>_mean_ms,
    # fleet_pipeline_*, fleet_serve_*, fleet_league_*, fleet_alerts_*,
    # fleet_incidents_total, fleet_topology_*. A family: ledger names,
    # tier names, and trace stages are data-dependent tails.
    "fleet_": "fleet telemetry rollups + conservation audit (dotaclient_tpu/obs/fleet.py)",
}


def is_registered(name: str) -> bool:
    return name in SCALARS or any(name.startswith(p) for p in PREFIXES)


def unregistered(names) -> list:
    """The subset of `names` no dashboard could know about — the drift
    guard's assertion payload. `step`/`time` are the JSONL record's own
    envelope fields, not scalars."""
    return sorted(n for n in names if n not in ("step", "time") and not is_registered(n))
