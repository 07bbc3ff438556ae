"""Flat dataclass configs, one per binary, overridable by CLI flags.

The reference configures each entrypoint with argparse flags and k8s env
vars and deliberately has no config framework (SURVEY.md §5 "Config / flag
system"); we mirror that: plain dataclasses + an argparse bridge.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
from dataclasses import dataclass, field


@dataclass
class PolicyConfig:
    """Architecture of the LSTM actor-critic (reference: policy.py)."""

    # Temporal core family: "lstm" (flagship, the reference architecture)
    # or "transformer" (long-context family: causal attention over the
    # chunk, chunk-local context, ring-shardable time axis —
    # models/transformer_policy.py).
    arch: str = "lstm"
    unit_embed_dim: int = 128
    lstm_hidden: int = 128  # temporal-core width (d_model for the transformer family)
    mlp_hidden: int = 128
    # Transformer-family shape (ignored for arch="lstm").
    tf_layers: int = 2
    tf_heads: int = 4
    # Actor KV-cache capacity. Invariant (enforced in make_actor_step):
    # >= rollout_len — the actor steps at most rollout_len frames per
    # chunk before next_chunk resets the cache (the bootstrap obs is
    # never stepped). Default leaves one slot of headroom over the
    # default rollout_len=16.
    tf_context: int = 17
    # Learner-side sequence parallelism: name of the mesh axis to shard
    # the time dimension over ("" = off). Engages ring attention
    # (ops/ring_attention.py) inside the unroll; requires the unrolled
    # frame count (seq_len+1) to divide by the axis size.
    tf_sp_axis: str = ""
    # Collective pattern for sequence-parallel attention: "ring"
    # (ppermute K/V streaming, any topology, no head constraint) or
    # "ulysses" (all-to-all head re-sharding; needs tf_heads divisible
    # by the sp axis). Same math either way — ops/ring_attention.py.
    tf_sp_mode: str = "ring"
    # Blocked (flash-formulation) LOCAL attention in the learner unroll:
    # > 0 turns it on wherever the key axis exceeds this size, 0 = dense.
    # It is the query and key block of the plain path (peak
    # intermediates [N, block, keys of a block's range] instead of
    # [N, T, T]), which runs on the CPU, in the ulysses SP path (whose
    # per-head-group attention sees the full time axis) and on shapes the
    # kernel refuses. On a TPU, with heads of 64 or a multiple of 128 and
    # T a multiple of 128, the blocked case goes through the fused Pallas
    # kernel instead, which chooses its own tiles (ops/attention.py
    # fused_tiles; ops/ring_attention.py fused_applies has the rule).
    # The ring is blockwise by construction and ignores this.
    tf_attn_block: int = 0
    # Rematerialize transformer blocks in the learner unroll
    # (jax.checkpoint): activations are recomputed in the backward
    # instead of stored, trading ~1/3 more FLOPs for O(L) less
    # activation memory — the standard long-context lever. No effect on
    # actor stepping (no backward) or on the math (tested identical).
    tf_remat: bool = False
    # The block's own sizes, as a published model's config states them;
    # at these defaults the block is the family's first one (pre-LN,
    # heads of lstm_hidden // tf_heads, full causal attention, a dense
    # GELU MLP of 4x, rotary base 10,000, biases, no final norm).
    tf_kv_heads: int = 0  # key/value heads (grouped-query attention); 0 = tf_heads
    tf_head_dim: int = 0  # width of one head; 0 = lstm_hidden // tf_heads
    # Kind of each layer, a comma list repeated over tf_layers: "full"
    # (causal) or "sliding" (causal, the last tf_window keys). "" = all full.
    # "latent" (every layer, or none): causal attention whose queries and
    # keys/values go through low-rank latents (tf_q_lora_rank and the four
    # sizes after it, below).
    # "gated": a full layer with an RMSNorm on each head's q and k and a
    # sigmoid gate on the heads' output, a column block of `qkv` beside q's.
    # "linear": a gated-delta-rule layer (ops/gated_delta.py; tf_lin_* below),
    # whose cost is linear in the chunk and whose state is a matrix a head.
    # "conv": a gated short-convolution layer in attention's place
    # (transformer_policy._short_conv), whose state is its last two gated
    # inputs.
    tf_layer_kinds: str = ""
    tf_window: int = 0  # keys a query of a sliding layer sees, itself included
    tf_rope_theta: float = 10000.0  # rotary base, both kinds
    tf_rotary_dim: int = 0  # lanes of a full/sliding/gated head that rotate, from lane 0; 0 = the whole head
    # A norm of tf_norm's kind on each head's q and k of a full or sliding
    # layer, before the rotation (one weight vector for all heads); the
    # gated kind has it whatever this says.
    tf_qk_norm: bool = False
    # YaRN on the full layers' rotary table (0 = the default table there
    # too): inverse frequencies blended between the default and default /
    # factor by the linear ramp over the correction range of (beta_fast,
    # beta_slow) at the original context; cos and sin times
    # 0.1 ln(factor) + 1 (ops/attention.py rope_table).
    tf_yarn_factor: float = 0.0
    tf_yarn_original_context: int = 0
    tf_yarn_beta_fast: float = 32.0
    tf_yarn_beta_slow: float = 1.0
    tf_norm: str = "layernorm"  # or "rmsnorm"
    tf_norm_eps: float = 1e-6
    tf_bias: bool = True  # biases on the block's projections
    tf_final_norm: bool = False  # a norm after the last block
    # Latent attention's sizes (tf_layer_kinds "latent"; tf_heads heads, no
    # grouping): queries through a normed latent of tf_q_lora_rank; keys
    # and values from a normed latent of tf_kv_lora_rank, which with one
    # rotary key of tf_qk_rope_dim shared by every head is all the actor's
    # cache holds; a head's query and key are tf_qk_nope_dim unrotated
    # dimensions beside the rotary ones, its value tf_v_head_dim.
    tf_q_lora_rank: int = 0
    tf_kv_lora_rank: int = 0
    tf_qk_nope_dim: int = 0
    tf_qk_rope_dim: int = 0
    tf_v_head_dim: int = 0
    # A linear layer's sizes: tf_lin_key_heads key heads serve
    # tf_lin_value_heads value heads (a multiple) of tf_lin_head_dim (keys',
    # queries' and values' alike); q, k and v pass a causal depthwise
    # convolution over the last tf_lin_conv frames. The actor's state is a
    # [tf_lin_head_dim, tf_lin_head_dim] matrix a value head and the
    # convolution's last tf_lin_conv - 1 inputs, and nothing per frame.
    tf_lin_key_heads: int = 0
    tf_lin_value_heads: int = 0
    tf_lin_head_dim: int = 0
    tf_lin_conv: int = 4
    # The dense feed-forward block: "gelu" = two matrices around a GELU,
    # "swiglu" = (silu(x Wg) * (x Wu)) Wd; width tf_mlp_hidden, 0 = 4x
    # lstm_hidden. With routed experts the first tf_dense_layers layers
    # keep the dense block and the layers after them are sparse.
    tf_mlp_act: str = "gelu"
    tf_mlp_hidden: int = 0
    tf_dense_layers: int = 0
    # Routed-expert feed-forward layer in place of the dense MLP
    # (ops/moe.py); 0 experts = the dense MLP. The router scores all
    # moe_experts; this process holds moe_experts_held of them (0 = all),
    # from moe_first_expert on, and computes their part of the sum: the
    # share of an expert-parallel group that one chip holds.
    moe_experts: int = 0
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    moe_top_k: int = 0  # experts a frame is routed to
    moe_hidden: int = 0  # width of one expert (SwiGLU)
    # A shared expert beside the routed ones (SwiGLU of this width, 0 =
    # none): every frame goes through it, on every chip alike, so it is no
    # part of the share and stands outside the routed layer.
    moe_shared_hidden: int = 0
    moe_shared_gate: bool = False  # the shared expert's output times a sigmoid of one product of the frame
    # The router's form (ops/moe.py route). "softmax": softmax over all
    # experts, the top_k largest, renormalised. "sigmoid": a sigmoid of
    # each score, the top_k largest of score + a per-expert bias that
    # chooses and does not weigh (kept in the tree and not trained by the
    # loss), the chosen scores renormalised and times moe_route_scale.
    moe_score: str = "softmax"
    moe_route_scale: float = 1.0
    # The router's scores are standardised per expert before the softmax:
    # less their running mean, over the root of the running mean of that
    # difference's square, over the chunk's frames so far (causal: the actor's step carries the
    # sums and agrees with the learner's unroll). Under seeded weights the
    # residual stream is mostly one vector that every frame shares (a relu
    # trunk's output, then the attention layers' averages) and the rest
    # favours some experts several times over others; a trained model's
    # load-balancing loss prevents both, and this stands in for it.
    moe_standardize_router: bool = False
    # Grouped products of the expert layer (ops/moe.py): "auto" = the
    # Pallas grouped-matmul kernel where the program runs on a TPU,
    # jax.lax.ragged_dot elsewhere; "ragged_dot" | "megablox" |
    # "megablox_interpret" (the kernel on the CPU, for tests) as asked.
    moe_impl: str = "auto"
    n_move_bins: int = 9  # 9-way discretized move offsets per axis
    move_step: float = 350.0  # map units per outermost move-grid cell
    # Auxiliary value heads (benchmark config 5: win-prob, last-hit, net-worth).
    aux_heads: bool = False
    dtype: str = "bfloat16"  # compute dtype on TPU; params stay f32
    # LSTM recurrence implementation (ops/lstm.py resolve_impl): "auto"
    # = the fused Pallas kernel where the program runs on a TPU with
    # 128 <= lstm_hidden < 512, W_h unsharded (no tp axis > 1) and a
    # batch slab that fits VMEM, lax.scan elsewhere; the learner logs
    # its choice once at build. "scan" | "pallas" | "pallas_interpret"
    # are taken as asked — "pallas" where it cannot run is an error.
    lstm_impl: str = "auto"


@dataclass
class PPOConfig:
    """PPO + GAE hyperparameters (reference: optimizer.py)."""

    gamma: float = 0.98
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    value_coef: float = 0.5
    value_clip: float = 0.2
    entropy_coef: float = 0.01
    lr: float = 1e-4
    adam_eps: float = 1e-5
    max_grad_norm: float = 0.5
    # Experience older than this many learner versions is dropped on the host
    # (reference drops/weights stale experience by model version).
    max_staleness: int = 4
    # Sample reuse (classic PPO): each consumed batch drives
    # epochs x minibatches gradient updates inside ONE compiled step —
    # advantages/returns computed once from the pre-update policy, then a
    # lax.scan over per-epoch shuffles and minibatch slices. At TPU speed
    # the learner is data-starved (device sits idle waiting for actors),
    # so reuse converts idle FLOPs into sample efficiency. 1/1 = the
    # single-update path (exactly the previous behavior).
    epochs: int = 1
    minibatches: int = 1
    # Approximate-KL early stop: when > 0, once a minibatch update's
    # approx_kl exceeds this, the REMAINING updates for the batch are
    # skipped (lax.cond no-ops — semantics of the classic mid-loop
    # `break`, with static shapes). 0 disables. Typical: 0.03.
    kl_stop: float = 0.0
    # ACER-style truncated-importance-weight cap (c-bar, arxiv 1611.01224)
    # applied to REPLAYED rows only: where a batch row's stamped
    # behavior-policy staleness is > 0, the IS ratio entering the clipped
    # surrogate is min(ratio, replay_rho_bar) — bounding the variance of
    # stale-ratio gradients (the A<0, ratio>>1 corner plain PPO clipping
    # leaves unbounded). Fresh rows (staleness 0) are untouched, so with
    # replay disabled the loss is bit-identical to plain PPO.
    replay_rho_bar: float = 2.0


@dataclass
class ReplayConfig:
    """Host-side prioritized replay reservoir between staging and the
    learner (dotaclient_tpu/replay/). Default OFF: with enabled=False the
    staging/learner data plane is bit-identical to the drop-on-stale
    pipeline (reference behavior)."""

    # Master switch. When on, rollouts that aged past ppo.max_staleness
    # (previously dropped on the host) are retained in the reservoir and
    # re-sampled into batches with ACER truncated importance weights.
    enabled: bool = False
    # Target fraction of each packed batch drawn from the reservoir
    # (0 <= ratio < 1); the rest stays fresh-from-the-broker. Batches
    # never block on the reservoir — a short reservoir just means more
    # fresh rows.
    ratio: float = 0.25
    # The reservoir's OWN staleness window, in learner versions: frames
    # older than this are expired/rejected outright (the pre-replay drop).
    # Must exceed ppo.max_staleness to retain anything.
    max_staleness: int = 32
    # Hard bound on resident reservoir bytes (serialized-frame sizes);
    # lowest-priority entries are evicted first. Default 256 MiB.
    byte_budget: int = 256 << 20
    # PER priority exponent on the |TD-error| key (0 = uniform).
    alpha: float = 0.6
    # Age decay half-life for sampling/eviction priority, in learner
    # versions: an entry this many versions old weighs half as much.
    age_half_life: float = 8.0
    # Per-entry sample cap before retirement (0 = unlimited): bounds how
    # often one surprising chunk can recur in the gradient.
    max_replays: int = 4
    # Compressed spill of cold entries: once occupancy crosses
    # spill_threshold * byte_budget, the coldest entries are zlib-
    # compressed in place (still sampleable), buying headroom before
    # eviction has to throw data away.
    spill_compress: bool = True
    spill_threshold: float = 0.5


@dataclass
class StagingConfig:
    """Parallel host feed (runtime/staging.py): multi-worker sharded
    pack into a ring of preallocated transfer buffers. Default
    pack_workers=1 keeps the single-consumer-thread staging path
    byte-for-byte (no pool threads, no ring — the inertness contract;
    tests/test_staging.py proves it in a subprocess)."""

    # Packer worker threads. 1 (default) = the classic path: one
    # consumer thread pops, parses, and packs inline. N>1 = the parallel
    # feed: a dedicated pop thread keeps draining the broker, an
    # assembler thread parses/filters (the batched C header parse
    # releases the GIL), and N pool workers each pack a disjoint
    # row-slice of the SAME transfer buffer concurrently (the C packer
    # releases the GIL — real parallelism). Output is BITWISE identical
    # to the single-thread pack for any worker count and any row split.
    # Sizing rule (README "Host feed pipeline"): ~1 worker per 4 host
    # cores feeding the learner, capped at 4 — pack is memcpy-bound, so
    # workers beyond the memory bandwidth knee only add contention.
    pack_workers: int = 1
    # Transfer-buffer ring depth (fused-H2D mode, pack_workers > 1
    # only): preallocated buffer sets with explicit ownership handoff
    # (free → packing → ready → in-transfer → free), so pack of batch
    # N+1 overlaps the H2D of N and the device step of N-1. The
    # learner's fetch returns a lease released once the device_put
    # retires. 2 = classic double buffering; raise it only if H2D
    # latency (not pack) is the longest stage.
    transfer_depth: int = 2
    # In-network batch assembly (--staging.assemble): consume DTB1
    # blocks of rows the fabric shards already packed into the native
    # row layout (shards run --broker.assemble); the learner-side pack
    # collapses to a per-row memcpy into a TransferRing slot. Requires
    # the fused-H2D path (the assembled rows ARE the transfer layout)
    # and pack_workers=1 (there is nothing left for a pool to do).
    # Default off keeps the classic consume path byte-for-byte.
    assemble: bool = False


@dataclass
class WireConfig:
    """Experience-wire quantization (transport/serialize.py DTR3).
    Producer-side only — consumers (staging, the native packer) accept
    DTR1/2/3 unconditionally, so the rolling-upgrade order is
    consumers-first: roll the learner, then flip actors to bf16."""

    # Wire dtype of the float obs leaves in published rollout frames:
    # "f32" (default) ships byte-identical legacy DTR1/DTR2 frames;
    # "bf16" casts obs f32→bf16 AT THE SOURCE (the exact RNE rounding
    # staging's compute-dtype cast applies anyway, so the TrainBatch is
    # bitwise unchanged) and ships DTR3 — roughly halving broker queue
    # memory, wire bandwidth, and staging intake bytes
    # (WIRE_QUANT_AB.json). Pinned f32 in prod manifests until the soak.
    obs_dtype: str = "f32"


@dataclass
class ServeConfig:
    """Centralized inference service — SERVER-side knobs (the
    `python -m dotaclient_tpu.serve.server` binary; dotaclient_tpu/serve/).
    The server owns one param tree, holds per-client LSTM carries
    resident, and runs continuous batching over a bounded gather window
    (the PR-5 InferenceBatcher semantics: fire at capacity or
    gather_window_s after the tick's first request, pad partial ticks to
    ONE jit signature, drop pad rows)."""

    # TCP port the inference service listens on (0 = pick a free port,
    # bench/test use; the k8s Service pins 13380).
    port: int = 13380
    # Batch capacity of one inference tick — the jit signature's row
    # count. Size to the expected concurrent in-flight requests (the
    # fan-in env count); partial ticks pad up to this, so oversizing
    # costs pad-row FLOPs, undersizing costs extra ticks.
    max_batch: int = 16
    # Bounded gather window, seconds: a tick fires at capacity or this
    # long after its FIRST request — one slow client stalls only itself.
    gather_window_s: float = 0.005
    # Cadence of the weight-fanout poll (the server subscribes to the
    # same broker weight fanout actors use; WeightPublisher's
    # on_published hook can poke the poll awake for same-tick swaps).
    weight_poll_s: float = 0.5
    # Session continuity (serve/handoff.py): "host:port" of the shared
    # carry store this replica streams (client_key, carry, version,
    # episode_step) deltas to at every chunk-boundary step — the
    # write-ahead happens BEFORE the chunk-fill reply, so a boundary a
    # client observed is always durably restorable. "" (default) = off:
    # no store connection, no extra bytes, replica death abandons
    # in-flight episodes exactly like PR-10. Requires fleet-unique
    # client keys (the actor_id scheme already guarantees this).
    # A COMMA list ("s0:13390,s1:13390") shards the store by rendezvous
    # hash of client_key (ShardedCarryStore): puts go to the key's
    # primary, failover reads walk the key's full preference order so
    # boundaries written before a shard ADD stay restorable. One
    # endpoint (no comma) is byte-for-byte the PR-13 single-store path.
    handoff_endpoint: str = ""
    # Per-RPC budget against the carry store. A store outage never
    # stops serving: the write is skipped (counted in
    # serve_handoff_store_errors_total) and the affected sessions
    # degrade to the PR-10 abandon semantics on the next failover.
    handoff_timeout_s: float = 2.0
    # Resident model slots. 1 (default) is byte-identical to the
    # single-model server: one live tree, no per-model anything. N > 1
    # adds N-1 FROZEN slots (league opponents) behind the same wire
    # port: slot 0 stays the live hot-swapped tree, slots 1..N-1 are
    # installed via swap_model() or synced from a league service
    # (--serve.league_endpoint). Each slot gets its own continuous
    # batcher (per-model tick bundles) sharing ONE compiled jit
    # signature — extra slots cost memory, not compiles.
    models: int = 1
    # League service "host:port" to sync frozen slots from (GET
    # /assignments → slot map, GET /snapshot → params). "" (default) =
    # no sync: slots hold their boot init until swap_model() is called
    # in-process. Ignored with --serve.models 1.
    league_endpoint: str = ""
    # Cadence of the league assignment poll, seconds.
    league_sync_s: float = 5.0


@dataclass
class ServeClientConfig:
    """Centralized inference service — ACTOR-side opt-in
    (dotaclient_tpu/serve/client.py). Default OFF: with endpoint empty
    the actor's inference hot path is byte-identical to the local jit
    path (the serve package is never imported — subprocess inertness
    proof in tests/test_serve.py)."""

    # Inference-service endpoint(s): "host:port" or a comma-separated
    # failover list "h1:p1,h2:p2,...". Each client STICKS to one replica
    # (server-side carry residency demands affinity) and fails over to
    # the next healthy one on connection loss or reply-deadline expiry
    # — in-flight episodes are abandoned (the UNKNOWN_CLIENT semantics),
    # never split across replicas. "" (default) = local inference,
    # exactly the pre-serve actor. Malformed lists fail loudly at boot.
    endpoint: str = ""
    # Per-request reply timeout, seconds: a server that dies without RST
    # must surface as a retryable RemoteInferenceError, not a hung env.
    timeout_s: float = 30.0
    # Per-dial TCP connect + handshake timeout, seconds. Deliberately
    # much shorter than timeout_s: a failover pass tries every healthy
    # endpoint in sequence, and each dead-but-blackholed replica costs
    # one of these.
    connect_timeout_s: float = 5.0
    # Seconds a failed endpoint sits out of the rotation before it is
    # probed again — a flapping replica is not hammered, and a fleet's
    # return-to-remote probes pace at this cadence.
    cooldown_s: float = 5.0
    # Graceful degradation: keep a broker-fanout-refreshed LOCAL param
    # tree warm, and when EVERY endpoint has been down for longer than
    # fallback_after_s, step episodes locally (versions stamped from the
    # local tree under the PR-5 chunk-boundary rule) until an endpoint
    # recovers — the fleet never stops generating experience, it just
    # pays local compute. Default off: remote-only actors keep params=()
    # and never pay a local init/compile.
    fallback_local: bool = False
    # All-endpoints-down budget before the local fallback engages,
    # seconds. Size it to ride out a single replica restart (failover
    # already covers those when a sibling replica is up): engaging is
    # cheap but flips the fleet off the accelerator tier.
    fallback_after_s: float = 10.0
    # Session continuity (the server side is --serve.handoff_endpoint):
    # with resume on, a remote-inference failure mid-episode no longer
    # abandons the episode — the client reconnects (failing over if
    # needed), presents its session (client_key + last chunk-boundary
    # step), the new replica restores the boundary carry from the
    # shared store, and the client REPLAYS its buffered partial-chunk
    # observations to rebuild the mid-chunk carry bitwise (at most one
    # chunk of recompute; replay outputs are discarded — the env
    # already acted on the originals). Default off: failure semantics
    # are byte-identical to PR-10 (abandon + ledger).
    resume: bool = False
    # Wall budget for one resume procedure (reconnect + restore +
    # replay, retried across failovers). Past it the episode abandons —
    # the PR-10 path. Keep it under fallback_after_s when both are
    # armed, or the fallback decision starves behind resume retries.
    resume_window_s: float = 20.0
    # Endpoint placement at (re)connect time: "order" (PR-10 list-order
    # rotation, the default) or "load" — probe every in-rotation
    # endpoint's S_INFO load report (connected clients + tick occupancy
    # from the actor_tick_rows_* histogram) and dial the least-loaded.
    # Affinity is untouched: the pick happens only when a connection is
    # (re)established, never mid-episode.
    route: str = "order"
    # Model id this client's sessions step against (multi-model serve).
    # 0 (default) = the live hot-swapped tree, and the S_INFO handshake
    # payload stays EMPTY — byte-identical to the single-model client
    # on every frame (the inertness rule; rollback = this flag). N > 0
    # binds the connection to frozen serve slot N (a league opponent);
    # a server without that slot resident refuses at handshake, loudly.
    model: int = 0
    # League service "host:port" (dotaclient_tpu/league/server.py).
    # League-opponent fleets (--opponent league + --serve.endpoint) ask
    # it GET /match at each episode for an opponent model id and POST
    # /result with the outcome — the matchmaking/rating loop. "" with
    # --serve.model 0 keeps the league fleet refusal (no served
    # opponents to play).
    league: str = ""


@dataclass
class RetryConfig:
    """Broker-client retry policy (transport/base.py RetryPolicy): one
    policy shared by the tcp transport's reconnect loop and the actor's
    SHED throttle, so a fleet tunes its backpressure behavior in ONE
    place. The jitter exists for the thundering-herd case: 256 actors
    whose broker restarts must not reconnect (or resume publishing after
    a shed) in lockstep."""

    # Seconds a failed broker request keeps reconnect-retrying before
    # giving up and raising (the old hardcoded _Conn retry_window).
    window_s: float = 60.0
    # First backoff sleep; doubles per attempt up to cap_s.
    backoff_base_s: float = 0.1
    backoff_cap_s: float = 2.0
    # Uniform jitter fraction: each sleep is drawn from
    # [b*(1-jitter), b*(1+jitter)]. 0 = the old deterministic lockstep.
    jitter: float = 0.5


@dataclass
class CkptConfig:
    """Preemption-tolerant checkpointing (runtime/checkpoint.py aux
    manifest + runtime/learner.py drain). Default OFF on every switch:
    with the defaults, checkpoint bytes on disk and the step loop are
    byte-identical to the params/opt/step-only behavior (asserted by the
    resume soak's inertness proof), so a rolling upgrade can land this
    build before any deployment opts in."""

    # Transactional full-state checkpoints: alongside the orbax step, an
    # aux manifest (written tmp + fsync + os.replace, so a crash mid-save
    # leaves the previous step fully restorable) captures the host RNG
    # streams, the replay-reservoir contents/priorities/staleness stamps,
    # staged-but-untrained pending frames, and the weight-publisher
    # version high-water mark — everything a learner kill would otherwise
    # lose. Restore re-injects all of it, and bumps the version counter
    # to the published high-water mark so in-flight rollout staleness
    # stamps stay monotonic (never under-aged for max_staleness/ACER).
    full_state: bool = False
    # Move the checkpoint off the step critical path: the loop thread
    # only dispatches an on-device state copy (async, donation-safe —
    # same stream-ordering argument as the weight publisher's
    # ParamFlattener); a dedicated worker thread pays the blocking host
    # read + reservoir snapshot + orbax/aux write, latest-wins coalesced.
    async_save: bool = False
    # Install a SIGTERM handler (learner main only): stop fetching,
    # finish the in-flight step, train out already-staged batches, save
    # full state with wait=True, exit 0 — the k8s preemption drain. The
    # matching manifests pair terminationGracePeriodSeconds/preStop with
    # drain_budget_s.
    drain_on_sigterm: bool = False
    # Hard wall-clock budget for the SIGTERM drain: a watchdog timer
    # force-exits (nonzero) if the drain has not completed by then, so a
    # wedged save can never outlive the pod's grace period into SIGKILL
    # with a half-written step.
    drain_budget_s: float = 45.0


@dataclass
class ChaosConfig:
    """Seeded fault injection (dotaclient_tpu/chaos/). Default OFF and
    import-free: with enabled=False no chaos module is ever imported and
    the broker/env objects are exactly the production ones —
    byte-identical wire behavior (asserted in tests/test_chaos.py)."""

    # Master switch: wrap this binary's broker in a ChaosBroker driving
    # the schedule below. NEVER set in production manifests (k8s pins it
    # false explicitly so a copy-pasted soak flag can't leak in).
    enabled: bool = False
    # Seed for every fault decision: same seed + spec -> the same faults
    # at the same operation indices (reproducible failure hunts).
    seed: int = 0
    # Fault schedule spec, e.g.
    # "latency:0.002~0.001,corrupt:0.01,dup:0.02,reset:0.005,
    #  stall@8:1.5,kill@10:2,kill@25:2" (chaos/schedule.py docstring is
    # the grammar). Empty = no faults even when enabled.
    spec: str = ""


@dataclass
class WatchdogConfig:
    """Learner liveness watchdog (dotaclient_tpu/obs/watchdog.py): a
    side thread that reads MetricsLogger.latest() + live gauges and
    escalates on stall / input starvation / NaN loss / steps/s
    regression: log -> flight-recorder dump -> flip /healthz to 503 (so
    a k8s liveness probe restarts the pod). Default OFF; requires
    obs.enabled."""

    enabled: bool = False
    # Seconds between checks (also the granularity of every window below).
    interval_s: float = 5.0
    # STALL: no learner-version advance for this many seconds. Must
    # comfortably exceed a worst-case batch wait + checkpoint write.
    stall_s: float = 120.0
    # Until the FIRST version advance the stall threshold is
    # max(stall_s, boot_grace_s): cold start legitimately spends minutes
    # in compile + checkpoint restore + waiting for the first published
    # rollouts, and a 120s stall_s would trip /healthz into a liveness
    # restart that replays the identical slow boot — an unbounded
    # crashloop. 600s covers multihost cluster formation with margin.
    boot_grace_s: float = 600.0
    # STARVATION: fraction of recent step wall time spent in the fetch
    # phase (compute_phase_fetch_frac) above this for consecutive checks.
    # 0 disables — the DEFAULT, deliberately: starvation is usually an
    # UPSTREAM failure (actors dead, fleet undersized) and restarting the
    # learner adds no actors; a single-actor smoke trips it instantly.
    # Opt in where a restart genuinely helps (wedged broker consumer) —
    # the k8s manifests set 0.95 against a sized actor fleet. Needs obs
    # step phases (the scalar it reads), so it is inert when
    # step_phases is off.
    starvation_frac: float = 0.0
    # NaN/inf guard on the latest logged `loss`. On by default when the
    # watchdog is on: a NaN loss never self-heals, restart is correct.
    nan_check: bool = True
    # REGRESSION: current env_steps_per_sec below this fraction of the
    # trailing-window median. 0 disables (CI smokes and phased drivers
    # have legitimately spiky rates).
    regression_frac: float = 0.0
    # Trailing window (number of metric samples) the regression baseline
    # is computed over.
    window: int = 12
    # Consecutive failing checks before each escalation stage: strike 1
    # logs, strike `dump_after` dumps the flight recorder, strike
    # `trip_after` flips /healthz to 503.
    dump_after: int = 2
    trip_after: int = 3


@dataclass
class ObsConfig:
    """Pipeline observability (dotaclient_tpu/obs/): rollout tracing,
    flight recorder, and the /metrics scrape endpoint. Default OFF with
    zero hot-path overhead: no trace stamping (wire frames stay
    byte-identical legacy DTR1), no hop recording, no ring writes, no
    HTTP server. Shared by the actor and learner binaries (--obs.*)."""

    # Master switch: stamp trace ids on published rollouts (actor),
    # record per-hop pipeline events + flight-recorder ring (both).
    enabled: bool = False
    # HTTP /metrics port, Prometheus text format (0 = no server). Serves
    # the latest MetricsLogger scalars plus live obs gauges (broker
    # queue depth, staging occupancy, replay reservoir stats). Stdlib
    # http.server only — no new dependencies.
    metrics_port: int = 0
    # Bounded in-memory ring of recent pipeline events per process,
    # dumped to JSON on crash, BatchLayoutError, SIGTERM, or explicit
    # FlightRecorder.dump().
    ring_size: int = 2048
    # Where flight-recorder dumps land ("" = current working directory).
    dump_dir: str = ""
    # Install process-wide SIGTERM + excepthook dump triggers. On by
    # default when obs is enabled; off for embedders (tests, drivers)
    # that own their signal handling.
    install_handlers: bool = True
    # Learner step-phase decomposition (obs/compute.py StepPhaseTimer):
    # logged as compute_phase_* and pipeline_* scalars. The prefetch
    # lane records its own fetch/pack/h2d (fenced there: the lane's own
    # time, hidden behind the device step), the loop thread records
    # take-wait/residual/host, and the phases tile the wall. The loop
    # itself is never fenced per step.
    step_phases: bool = True
    # Where POST /profile?seconds=N captures land (jax.profiler.trace
    # TensorBoard dirs). "" = dump_dir (or cwd).
    profile_dir: str = ""
    # Hard cap on a single on-demand profile capture; /profile clamps to
    # this (an unbounded capture would fill the pod disk).
    profile_max_seconds: float = 60.0
    # Liveness watchdog (obs/watchdog.py) — learner only.
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)


@dataclass
class LearnerConfig:
    """Learner binary (reference: optimizer.py CLI).

    The feed is two boxes, lane -> loop (runtime/learner.py): a prefetch
    thread stages batch N+1 while the device runs step N. A batch
    crosses to the device as ONE [B, row_bytes] u8 buffer
    (parallel/fused_io.py); the Learner takes the per-leaf tree instead
    where that layout cannot apply (a sequence-parallel mesh, the replay
    reservoir), from the mesh and the config, with no option."""

    batch_size: int = 256  # sequences per train step (global, across dp shards)
    seq_len: int = 16  # rollout chunk length = LSTM truncation window
    ppo: PPOConfig = field(default_factory=PPOConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    policy: PolicyConfig = field(default_factory=PolicyConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    broker_url: str = "mem://"
    # Broker-fabric shard subset this learner consumes, as a comma-
    # separated index list into the --broker_url shard list ("0,1").
    # "" (default) = consume every shard. Only valid when --broker_url
    # is itself a comma-separated shard list (transport/fabric.py); the
    # multi-learner data-parallel fan-in assigns each learner a DISJOINT
    # subset so the steady-state stream is partitioned exactly once.
    # Known limitation (documented, bounded): a producer FAILOVER
    # republish follows the key's rendezvous order, which can cross
    # subset boundaries — each learner's fence is per-consumer, so the
    # stale original and the republish can each train once, in
    # DIFFERENT learners. This is the same rare at-least-once duplicate
    # class the classic tcp reconnect resend has always had ("harmless
    # to PPO", transport/tcp.py _Conn), at publish-failover frequency.
    # Publishing (weight fanout) always reaches every shard regardless.
    broker_shards: str = ""
    checkpoint_dir: str = ""
    # Remote checkpoint mirror (reference behavior: upload finished
    # checkpoints to object storage — SURVEY §3.4). Any epath scheme
    # (gs://bucket/path, s3://...); each finished step is file-copied up
    # and a fresh learner with an empty checkpoint_dir pulls the newest
    # complete remote step back down (runtime/checkpoint.py).
    checkpoint_remote_dir: str = ""
    checkpoint_every: int = 100  # steps between durable checkpoints
    # Preemption tolerance (--ckpt.*): transactional full-state
    # checkpoints, async save, SIGTERM drain. All default off.
    ckpt: CkptConfig = field(default_factory=CkptConfig)
    publish_every: int = 1  # steps between weight fanout publishes
    # Rolling-upgrade transition flag (ADVICE r4): emit legacy DTW1
    # weight frames (no boot_epoch) so not-yet-upgraded subscribers keep
    # parsing while the fleet rolls. Compat is one-directional — new
    # readers accept DTW1 — so the safe order is: (1) learner with this
    # flag ON, (2) upgrade all actors/evaluators, (3) flag OFF to get
    # boot-epoch resync back. Costs restart-resync determinism while ON.
    publish_legacy_dtw1: bool = False
    # Steps between host↔device metric syncs. Fetching the metrics dict
    # forces a device sync; doing it every step serializes the host onto
    # the step's critical path (the round-2 e2e-vs-device gap). Scalars
    # are logged once per window with window-averaged timings.
    metrics_every: int = 10
    log_dir: str = ""
    seed: int = 0
    mesh_shape: str = "dp=-1"  # e.g. "dp=4,tp=2"; -1 = all remaining devices
    # C++ batch packer on the staging path (falls back to python when the
    # build/load fails or DOTACLIENT_TPU_NO_NATIVE=1 is set)
    native_packer: bool = True
    # Parallel host feed (--staging.pack_workers / --staging.transfer_depth).
    staging: StagingConfig = field(default_factory=StagingConfig)
    # Stage obs floats in the policy compute dtype (bf16) on the host:
    # numerically identical (the policy's first op is the same cast) and
    # halves the dominant host→device transfer (runtime/staging.py
    # cast_obs_to_compute_dtype). Off = ship f32 and cast on device.
    stage_obs_compute_dtype: bool = True
    # JAX backend of this process (runtime/device.py init_devices): ""
    # = JAX's default — JAX_PLATFORMS if set, else the best backend
    # present, which on a host without a chip is the CPU; the first log
    # line says which it was. A name ("tpu", "cpu") pins that backend,
    # and a pinned backend that is absent fails the boot.
    platform: str = ""
    # Multi-host learner (SURVEY.md §5 "Distributed communication
    # backend": jax.distributed over DCN if the learner ever spans
    # hosts). When true, jax.distributed.initialize() joins this process
    # to the cluster BEFORE backend init; jax.devices() then spans every
    # process's chips and the mesh/shardings work unchanged (XLA routes
    # intra-host collectives over ICI, cross-host over DCN). Each process
    # runs this same binary with its own process_id.
    multihost: bool = False
    # Each resolves independently: "" / -1 = let jax auto-detect from
    # cluster env or TPU metadata; set explicitly for manual clusters.
    coordinator: str = ""  # host:port of process 0
    num_processes: int = -1
    process_id: int = -1
    # Stop after this many train steps (0 = run forever). Smoke/CI use.
    train_steps: int = 0


@dataclass
class ActorConfig:
    """Actor binary (reference: agent.py CLI)."""

    env_addr: str = "localhost:13337"
    # "internal": this framework's env protos (fake env, tests);
    # "valve": a real dotaservice speaking CMsgBotWorldState — adapted at
    # the stub boundary (env/valve_adapter.py), actor loop unchanged.
    env_dialect: str = "internal"
    broker_url: str = "mem://"
    rollout_len: int = 16  # steps per published experience chunk
    host_timescale: float = 10.0
    ticks_per_observation: int = 30
    max_dota_time: float = 600.0
    hero: str = "npc_dota_hero_nevermore"
    # "scripted":      1v1 vs the env's passive scripted bot (runtime/actor.py)
    # "scripted_hard": 1v1 vs the hard scripted bot (farms + retreats) — the
    #                  north-star TrueSkill yardstick
    # "self":          mirror self-play, both sides live weights (runtime/selfplay.py)
    # "league":        PFSP league self-play vs frozen snapshots (eval/league.py)
    opponent: str = "scripted"
    # Heroes per team (1 = the 1v1 ladder rungs; 5 = BASELINE configs 4-5
    # team play). Self-play batches ALL controlled heroes into one jit
    # call per tick (B = 2*team_size mirror, B = team_size per side in
    # league mode) and publishes per-hero trajectories.
    team_size: int = 1
    league_capacity: int = 8  # max snapshots in the local league pool
    league_snapshot_every: int = 20  # learner versions between snapshots
    pfsp_mode: str = "hard"  # "hard" | "even" | "uniform"
    # Kill switch: exit (for supervisor restart) if no weight broadcast
    # arrives for this many seconds. 0 disables. Default ON (ADVICE r4):
    # with the switch disabled, a mixed-version deploy whose learner
    # emits frames this build can't parse (e.g. a future wire bump)
    # would silently freeze policy propagation cluster-wide — per-frame
    # warnings and an ever-staler policy. 900s is ~3 orders of magnitude
    # above the normal broadcast cadence and comfortably above learner
    # restart + checkpoint-restore time, so it only fires when
    # propagation is genuinely dead.
    max_weight_age_s: float = 900.0
    # Ablation: mask the CAST action out of every observation, so the
    # policy can never use abilities. Exists to measure whether ability
    # usage is ADVANTAGEOUS (scripts/ab_cast.py trains with and without);
    # never set in production.
    disable_cast: bool = False
    # Vectorized actor fleet (runtime/actor.py VectorActor): one process
    # drives this many env sessions on a single asyncio loop, gathering
    # their observations into ONE batched jit inference call per tick
    # (lax.map over rows — bit-identical to stepping each env alone) so
    # per-dispatch framework overhead amortizes across envs. 1 = the
    # classic one-env-per-process path, byte-for-byte unchanged.
    # ACTOR_FLEET.json holds the measured offered-rate curve that picks
    # the production default. Scripted opponents batch across envs;
    # self/league actors run envs_per_process concurrent sessions per
    # loop instead (each already batches its own heroes per jit call).
    envs_per_process: int = 1
    # Bounded gather window for the batched inference tick, seconds: the
    # batcher fires as soon as every env slot has submitted, and no later
    # than this after the FIRST submission of the tick — a slow gRPC
    # observe() can stall its own env, never the whole batch (partial
    # batches are padded to capacity and the pad rows' results dropped).
    gather_window_s: float = 0.005
    obs: ObsConfig = field(default_factory=ObsConfig)
    policy: PolicyConfig = field(default_factory=PolicyConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    # Experience-wire quantization (--wire.obs_dtype {f32,bf16}).
    wire: WireConfig = field(default_factory=WireConfig)
    # Centralized inference service opt-in (--serve.endpoint host:port):
    # ship featurized obs to a dedicated batching server instead of
    # running the policy locally. Default off = the local jit path,
    # byte-identical to the pre-serve build.
    serve: ServeClientConfig = field(default_factory=ServeClientConfig)
    seed: int = 0
    actor_id: int = 0
    # Actors are CPU processes (reference architecture: the accelerator
    # belongs to the learner, and a chip serves one process at a time).
    # Same semantics as LearnerConfig.platform.
    platform: str = "cpu"


@dataclass
class InferenceConfig:
    """Inference-service binary (dotaclient_tpu/serve/server.py): owns
    one param tree (init'd from --seed like an actor, hot-swapped from
    the broker weight fanout), serves batched policy steps to remote
    actors, and exports serve_* scalars on the obs scrape surface."""

    serve: ServeConfig = field(default_factory=ServeConfig)
    policy: PolicyConfig = field(default_factory=PolicyConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    # Weight-fanout source (same URL the actors use). The service is a
    # weights SUBSCRIBER only — experience never flows through it.
    broker_url: str = "mem://"
    # Param-init seed: must match the learner fleet's seed so the
    # service can serve from step zero (the actor-boot convention).
    seed: int = 0
    # "cpu" (default) pins the service to host devices; an inference
    # pod that owns a chip passes --platform tpu. Same semantics as
    # LearnerConfig.platform.
    platform: str = "cpu"


@dataclass
class HandoffConfig:
    """Carry-store binary (dotaclient_tpu/serve/handoff.py): the small
    replicated session-continuity store the inference replicas stream
    chunk-boundary carries to (--serve.handoff_endpoint) and read back
    on failover. Pure stdlib + numpy — it never builds a policy or
    touches jax, so it boots in milliseconds and can run as a tiny
    sidecar-class pod (k8s/inference.yaml `carry-store`)."""

    # TCP port the store listens on (0 = pick a free port, test use;
    # the k8s Service pins 13390).
    port: int = 13390
    # Entries retained per session key. 2 is load-bearing, not a cache
    # knob: the previous boundary must stay readable so a client whose
    # chunk-fill ACK was lost in a kill (store written, reply dead) can
    # still resume from the boundary it actually observed.
    keep: int = 2
    # The full store shard ring this pod belongs to, as the SAME comma
    # list the serve replicas get in --serve.handoff_endpoint ("" = a
    # single unsharded store). The store itself never routes — placement
    # is client-side rendezvous — but declaring the ring here makes the
    # pod's ready line name its topology, so a mis-rolled ring (pods
    # and replicas disagreeing about the shard list) is visible at boot
    # instead of surfacing as resume misses.
    stores: str = ""
    # /metrics + /healthz scrape surface (serve_handoff_store_* gauges).
    obs: ObsConfig = field(default_factory=ObsConfig)


@dataclass
class ControlLoopConfig:
    """The --control.* surface of the control-plane binary
    (dotaclient_tpu/control/server.py). All topology lists are comma
    `host:port` endpoint lists naming each tier's METRICS surfaces —
    the controller scrapes /metrics + /healthz there, decides against
    the policy, and actuates through the configured driver."""

    # Port of the controller's own HTTP surface: GET /topology (the
    # discovery endpoint actors and serve clients poll at (re)connect),
    # plus the standard /metrics + /healthz (control_* gauges). The k8s
    # Service pins 13400; 0 = pick a free port (test use).
    port: int = 13400
    # Scrape-decide-actuate cadence, seconds. Size against the policy
    # cooldowns (a poll period much longer than a cooldown makes the
    # cooldown a no-op; much shorter just re-reads unchanged gauges).
    poll_s: float = 2.0
    # Declarative scaling policy: ";"-separated clauses, each
    # "tier:meter,high=H,low=L,min=M,max=X,cooldown=C,step=S" — scale
    # `tier` up by `step` when `meter` > H (down when < L), clamped to
    # [M, X], at most one move per C seconds (control/policy.py). The
    # high/low gap is the hysteresis band (the --shed_high/--shed_low
    # watermark discipline applied to topology); "" = observe-only.
    policy: str = ""
    # Actuation driver: "static" observes and ledgers decisions without
    # actuating (the safe default — rollback is a driver flip, not a
    # rollout); "k8s" speaks `kubectl scale statefulset` against the
    # committed manifests. The in-process driver (soaks/tests) is
    # injected programmatically, never flag-selected.
    driver: str = "static"
    # Per-tier metrics endpoints the scraper polls (comma host:port
    # lists; "" = tier unmanaged). These are OBS ports, not data ports.
    brokers: str = ""
    servers: str = ""
    actors: str = ""
    stores: str = ""
    learner: str = ""
    # k8s driver scope: the namespace the StatefulSets live in, and the
    # kubectl binary to exec (tests point this at a recorder script).
    namespace: str = "dotaclient"
    kubectl: str = "kubectl"


@dataclass
class ControlConfig:
    """Control-plane binary (python -m dotaclient_tpu.control.server):
    the closed-loop autoscaler/router. Scrapes the fleet's existing
    Prometheus-text /metrics + /healthz surfaces, computes target
    replica counts per tier from the declarative policy, actuates via
    the pluggable driver, and serves /topology for discovery. Stdlib
    only — never imports jax or the wire stack."""

    control: ControlLoopConfig = field(default_factory=ControlLoopConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)


@dataclass
class FleetLoopConfig:
    """The --fleet.* surface of the fleet telemetry aggregator
    (python -m dotaclient_tpu.obs.fleetd): topology-driven scraping of
    every tier's /metrics surface, a continuous frame-conservation
    audit, fleet SLO rollups, and alert-triggered flight-recorder
    fan-in. Stdlib only — the controller's weight class."""

    # Port of fleetd's own HTTP surface: GET /fleet (the JSON rollup),
    # /metrics (fleet_* gauges the control plane can consume as policy
    # meters), /healthz (503 while any ledger is stale/alarming), and
    # /debug/flight. The k8s Service pins 13420; 0 = free port (tests).
    port: int = 13420
    # Scrape-audit-alert cadence, seconds. One poll = one audit window:
    # the injected-loss detection latency bound is exactly this.
    poll_s: float = 2.0
    # Per-target time-series ring length (poll windows retained for the
    # /fleet history view); bounds fleetd memory per target.
    window: int = 64
    # Seconds without a successful scrape before a target is reported
    # stale in /fleet (the audit freezes immediately either way).
    stale_s: float = 10.0
    # Control-plane address (host:port) whose GET /topology "metrics"
    # map is the discovery source; discovered endpoints MERGE with the
    # literal lists below. "" = literal lists only (the rollback
    # position, same semantics as --serve.endpoint).
    control: str = ""
    # Literal per-tier scrape lists (comma host:port of OBS surfaces;
    # "" = tier absent). These are the rollback position AND the way to
    # aggregate tiers the control plane does not manage.
    brokers: str = ""
    servers: str = ""
    actors: str = ""
    stores: str = ""
    learners: str = ""
    leagues: str = ""
    # Alert clauses: ";"-separated "meter,op,threshold,for=W" — meter
    # names fleetd's OWN rollup gauges (fleet_unaccounted_frames,
    # fleet_targets_up, ...), op in gt|ge|lt|le|eq|ne, W = consecutive
    # breached poll windows before firing. A firing edge snapshots
    # every target's GET /debug/flight ring into one incident bundle.
    # "" = audit-only (no alerting). Parse errors fail boot LOUDLY.
    alerts: str = ""
    # Directory incident bundles land in ("" = cwd).
    bundle_dir: str = ""


@dataclass
class FleetConfig:
    """Fleet telemetry binary (python -m dotaclient_tpu.obs.fleetd):
    the standing aggregator. Scrapes the fleet, audits the conservation
    ledgers live, serves fleet_* rollups. Stdlib only — never imports
    jax or the wire stack."""

    fleet: FleetLoopConfig = field(default_factory=FleetLoopConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)


@dataclass
class LeagueServiceConfig:
    """The --league.* surface of the standing league service
    (dotaclient_tpu/league/server.py): a disk-backed snapshot registry
    with checkpoint-lineage records, a matchmaking endpoint over the
    declarative policy grammar, and a TrueSkill rating service — the
    eval/league.py per-actor pool promoted to ONE queryable population
    shared by the whole fleet."""

    # Port of the service's HTTP surface: GET /match, /leaderboard,
    # /lineage, /assignments, /snapshot plus the standard /metrics +
    # /healthz (league_* gauges) and POST /result, /snapshot. The k8s
    # Service pins 13410; 0 = pick a free port (test use).
    port: int = 13410
    # Registry root: snapshots persist as <dir>/<name>.npz beside
    # lineage.json (the checkpoint-lineage ledger) and matches.jsonl
    # (the append-only match log the leaderboard is reproducible from).
    # "" = in-memory only (tests); a restart then loses the population.
    dir: str = ""
    # Opponent-pool capacity — also the number of frozen serve slots a
    # multi-model server needs (--serve.models = capacity + 1: slot 0
    # stays the live tree). Eviction past capacity is the eval/league.py
    # rule: weakest by mu, never the newest.
    capacity: int = 8
    # Serve model slots the service publishes assignments for (GET
    # /assignments maps slot 1..slots onto the most recent population
    # members; slot 0 is always the live tree and never assigned). Size
    # to the serve tier's --serve.models - 1.
    slots: int = 3
    # Admission cadence for fan-out-fed snapshots, learner versions
    # (the eval/league.py maybe_snapshot gating, version-regression
    # reset included).
    snapshot_every: int = 20
    # Matchmaking policy: ";"-separated weighted clauses
    # "kind[@weight]", kind ∈ uniform | prioritized | exploiter
    # (league/policy.py). Each GET /match draws a clause by weight:
    # uniform samples the pool flat, prioritized weights opponents by
    # observed loss rate (the PFSP-hard analog over ingested results),
    # exploiter assigns the caller the exploiter role vs the MAIN live
    # tree (model 0). E.g. "prioritized@0.7;exploiter@0.3".
    policy: str = "uniform"
    # The serve endpoint handed to /match callers ("host:port" of the
    # multi-model inference tier). The service never dials it — it is
    # matchmaking metadata, so fleets learn the serving address and the
    # opponent model id from ONE response.
    serve_endpoint: str = ""
    # Weight-fanout source feeding the registry (the WeightPublisher
    # broadcasts actors already receive). "" = no subscription: the
    # population grows only via POST /snapshot registrations.
    broker_url: str = ""
    # Fanout poll cadence, seconds.
    poll_s: float = 1.0
    # Exploiter promotion gate: an exploiter candidate whose ingested
    # results vs main reach gate_games matches AND gate_winrate wins
    # is promoted into the opponent pool (lineage event "promote").
    gate_games: int = 5
    gate_winrate: float = 0.55
    # Matchmaking draw seed (deterministic soaks/tests).
    seed: int = 0


@dataclass
class LeagueConfig:
    """League-service binary (python -m dotaclient_tpu.league.server).
    Like the control plane it is a standing HTTP service outside the
    data path — numpy for snapshot trees, stdlib for everything else;
    it never imports jax or the serve wire stack."""

    league: LeagueServiceConfig = field(default_factory=LeagueServiceConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)


@dataclass
class EvalConfig:
    """Evaluator binary (eval/evaluator.py): plays frozen-policy episodes
    vs the scripted bot on each fresh weight broadcast."""

    actor: ActorConfig = field(default_factory=ActorConfig)
    episodes: int = 16  # episodes per evaluation round
    eval_every: int = 10  # learner versions between evaluations
    log_dir: str = ""


def _parse_bool(s: str) -> bool:
    low = s.lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {s!r}")


def add_flags(parser: argparse.ArgumentParser, cfg, prefix: str = "") -> None:
    """Register one --flag per (possibly nested) dataclass field."""
    for f in dataclasses.fields(cfg):
        val = getattr(cfg, f.name)
        name = f"{prefix}{f.name}"
        if dataclasses.is_dataclass(val):
            add_flags(parser, val, prefix=f"{name}.")
        elif isinstance(val, bool):
            parser.add_argument(f"--{name}", type=_parse_bool, default=val)
        else:
            parser.add_argument(f"--{name}", type=type(val), default=val)


def parse_config(cfg, argv=None):
    """Parse CLI flags into a fresh deep copy of `cfg` (returns the copy)."""
    cfg = copy.deepcopy(cfg)
    parser = argparse.ArgumentParser()
    add_flags(parser, cfg)
    args = parser.parse_args(argv)
    _apply(cfg, vars(args))
    return cfg


def _apply(cfg, flat: dict, prefix: str = "") -> None:
    for f in dataclasses.fields(cfg):
        val = getattr(cfg, f.name)
        name = f"{prefix}{f.name}"
        if dataclasses.is_dataclass(val):
            _apply(val, flat, prefix=f"{name}.")
        elif name in flat:
            setattr(cfg, f.name, flat[name])
