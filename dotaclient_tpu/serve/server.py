"""Inference server: continuous batching + carry residency + hot-swap.

`python -m dotaclient_tpu.serve.server --serve.port 13380
 --broker_url tcp://broker:13370 --obs.enabled true --obs.metrics_port 9100`

One process owns one param tree and serves policy steps to remote
actors (serve/client.py) over the serve wire (serve/wire.py):

- **Continuous batching.** Requests from all connections funnel into a
  `_ServeBatcher` — the PR-5 `InferenceBatcher` (fire at capacity or
  `--serve.gather_window_s` after the tick's first request; pad partial
  ticks to ONE jit signature; drop pad rows) extended with a per-tick
  (params, version, tick) bundle. Row results are bitwise those of the
  standalone B=1 actor step (the lax.map occupancy-invariance contract),
  so remote actors publish byte-identical frames.

- **LSTM carry residency.** The server keeps each client's (c, h)
  resident, keyed by (connection, client_key): requests carry only the
  featurized obs + episode-boundary flags. EPISODE_START resets the
  carry to zeros; a disconnect evicts the connection's carries; a step
  naming an unknown key (server restarted, carry evicted) is answered
  UNKNOWN_CLIENT and the client abandons the episode — exactly the lost
  env-session semantics.

- **Weight hot-swap without draining.** The tree + version live in ONE
  tuple (`_bundle`) swapped by a single reference assignment; the
  batcher reads it ONCE per tick (`_tick_bundle`), so every row of a
  tick is served by one tree and clients can never observe a mixed
  tick — no drain, no pause, the swap lands between ticks. Swaps come
  from the broker weight fanout (a poll thread with the actor's
  `apply_weight_frame` staleness/epoch rules) or directly via
  `swap_params` — a co-located learner chains it off its
  WeightPublisher `on_published` hook (with `poke()` collapsing the
  poll latency to the next tick boundary).

Obs surface: `serve_*` scalars + the batcher's `actor_*` family
(including the `actor_tick_rows_<k>` occupancy histogram) on
`/metrics`, structured `/healthz` — registry-pinned in obs/registry.py.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from dotaclient_tpu.config import ActorConfig, InferenceConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.models import policy as P
from dotaclient_tpu.runtime.actor import InferenceBatcher, apply_weight_frame
from dotaclient_tpu.serve import wire as W

_log = logging.getLogger(__name__)


class _ServeBatcher(InferenceBatcher):
    """InferenceBatcher whose rows carry serving provenance: the tick's
    (params, version) bundle is read ONCE per tick, and every future
    resolves to (row, version, tick) — the hot-swap no-mixed-tick
    invariant is structural, not timed."""

    def __init__(self, cfg: ActorConfig, bundle_fn, capacity: int):
        # params_fn is unused by this subclass (_tick_bundle overrides
        # the read), but the base requires a callable.
        super().__init__(cfg, lambda: bundle_fn()[0], capacity=capacity)
        self._bundle_fn = bundle_fn
        self._tick_seq = 0

    def _tick_bundle(self):
        params, version = self._bundle_fn()  # ONE atomic tuple read
        self._tick_seq += 1
        return (params, version, self._tick_seq)

    def _row_result(self, out, i: int, bundle):
        return jax.tree.map(lambda x: x[i], out), bundle[1], bundle[2]


class _ClientConn:
    """Per-connection server state: the resident carries this connection
    owns and the write lock serializing interleaved responses. `steps`
    tracks each resident carry's episode position (completed steps;
    reset by EPISODE_START, installed by a session resume) — the
    episode_step the handoff store entries are stamped with. `model` is
    the serve slot the S_INFO handshake bound this connection to (0 =
    the live tree — the only value a legacy client can produce, since
    it sends the empty payload)."""

    def __init__(self, writer: asyncio.StreamWriter):
        self.writer = writer
        self.lock = asyncio.Lock()
        self.carries: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self.steps: Dict[int, int] = {}
        self.model = 0

    async def send(self, mtype: int, payload: bytes) -> None:
        try:
            async with self.lock:
                self.writer.write(W.frame(mtype, payload))
                await self.writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            # The client disconnected while a step was in flight: its
            # result dies with the connection (the env abandoned the
            # episode anyway); the reader side of _handle does eviction.
            pass


class InferenceServer:
    """Asyncio inference service; `start()` runs it in a daemon thread
    (the BrokerServer lifecycle pattern). Construction initializes the
    param tree deterministically from cfg.seed — the actor-boot
    convention, so the service answers from step zero while the first
    weight broadcast is still compiling."""

    def __init__(self, cfg: InferenceConfig, broker=None, obs_runtime=None, carry_store=None):
        if cfg.policy.arch != "lstm":
            raise ValueError(
                f"inference service requires policy.arch='lstm' (server-side "
                f"carry residency is (c, h)-keyed), got {cfg.policy.arch!r}"
            )
        self.cfg = cfg
        self.host = "0.0.0.0"
        self.port = int(cfg.serve.port)
        self.broker = broker
        # apply_weight_frame contract: params/version/weight_epoch/
        # last_weight_time live on the agent object.
        self.params = P.init_params(cfg.policy, jax.random.PRNGKey(cfg.seed))
        self.version = 0
        self.last_weight_time = time.monotonic()
        # THE hot-swap cell: (params, version) swapped by one reference
        # assignment (poller thread writes, batcher tick reads once) —
        # the atomically-rebound-and-read-once pattern. The tick READ
        # needs no lock; the WRITERS do: a co-located learner chains
        # swap_params off its WeightPublisher on_published hook while
        # the broker poll thread applies fanout frames, and two
        # unordered writers tear the (params, version) pair that
        # apply_weight_frame's staleness rules read-modify-write
        # (racecheck surfaced the write-write race on params/version/
        # _bundle/weight_swaps_total; graftcheck PR).
        self._swap_lock = threading.Lock()
        # Multi-model serve (--serve.models N): slot 0 is the live
        # hot-swapped tree (the only slot at N=1 — byte-identical to
        # the single-model server); slots 1..N-1 hold FROZEN trees
        # (league opponents) installed via swap_model()/the league
        # sync loop. Each slot is its own (params, version) hot-swap
        # cell read once per tick by its own batcher, so the
        # no-mixed-tick invariant holds PER MODEL.
        self.models = max(1, int(cfg.serve.models))
        self._bundles: list = [(self.params, self.version)]
        for _ in range(1, self.models):
            # frozen slots boot from the same seed init as slot 0 — the
            # deterministic boot convention; a sync/swap replaces them
            self._bundles.append((self.params, 0))
        # Batcher cfg: the serve knobs mapped onto the ActorConfig shape
        # InferenceBatcher speaks (gather window + policy).
        bcfg = ActorConfig(policy=cfg.policy, gather_window_s=cfg.serve.gather_window_s)
        self.batchers = [
            _ServeBatcher(
                bcfg, (lambda m=m: self._bundles[m]), capacity=cfg.serve.max_batch
            )
            for m in range(self.models)
        ]
        # ONE jit signature per arch across all models: every batcher
        # shares slot 0's compiled step (identical shapes/signature —
        # only the params argument differs per tick), so N models never
        # multiply compiles or the _warm() wall.
        for b in self.batchers[1:]:
            b._step = self.batchers[0]._step
        # Per-model ledgers (requests served / carries evicted / trees
        # swapped per slot) — flat int lists so the chaos controller's
        # getattr harvest and the soak's exactness cross-checks read
        # them like every other counter.
        self.model_requests = [0] * self.models
        self.model_evictions = [0] * self.models
        self.model_swaps = [0] * self.models
        self.league_syncs_total = 0
        self.league_sync_errors_total = 0
        self._synced: Dict[int, Tuple[str, int]] = {}  # slot → installed (name, version)
        self._stop_sync = threading.Event()
        self._sync_thread: Optional[threading.Thread] = None
        # Loop-thread-written counters; stats() takes GIL-atomic single
        # reads (the BrokerServer ledger pattern — exact after stop()).
        # first_request_t is the recovery probe (the broker
        # first_enqueue_t analog): monotonic time of the first SERVED
        # step since boot — ServeIncarnations turns kill-restart-this
        # into a failover recovery_s.
        self.first_request_t: Optional[float] = None
        self.requests_total = 0
        self.unknown_client_total = 0
        self.bad_requests_total = 0
        self.episode_resets_total = 0
        self.evictions_total = 0
        self.weight_swaps_total = 0
        # Session continuity (serve/handoff.py): the shared carry store
        # this replica write-ahead-streams chunk-boundary carries to.
        # `carry_store` injects any object with the CarryStoreClient
        # API (tests/soaks use LocalCarryStore); otherwise
        # --serve.handoff_endpoint builds the TCP client — and when
        # BOTH are unset the handoff module is never imported (the
        # serve tier's own inertness rule).
        if carry_store is None and cfg.serve.handoff_endpoint:
            if "," in str(cfg.serve.handoff_endpoint):
                # comma list = sharded ring: rendezvous placement by
                # client_key, full-preference-order failover reads
                from dotaclient_tpu.serve.handoff import ShardedCarryStore

                carry_store = ShardedCarryStore(
                    str(cfg.serve.handoff_endpoint),
                    timeout_s=cfg.serve.handoff_timeout_s,
                )
            else:
                from dotaclient_tpu.serve.handoff import CarryStoreClient

                host, sep, port = str(cfg.serve.handoff_endpoint).rpartition(":")
                if not sep or not port.isdigit():
                    raise ValueError(
                        f"--serve.handoff_endpoint must be host:port, got "
                        f"{cfg.serve.handoff_endpoint!r}"
                    )
                carry_store = CarryStoreClient(
                    host or "127.0.0.1", int(port), timeout_s=cfg.serve.handoff_timeout_s
                )
        self._store = carry_store
        self.handoff_writes_total = 0
        self.handoff_write_errors_total = 0
        self.resumes_total = 0
        self.resume_misses_total = 0
        self.replayed_steps_total = 0
        self._conns: set = set()  # live _ClientConn, loop-thread mutated
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server = None
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        self._boot_error: Optional[BaseException] = None
        self._stop_poll = threading.Event()
        self._poke = threading.Event()
        self._poll_thread: Optional[threading.Thread] = None
        self.obs = obs_runtime

    # ------------------------------------------------------------ weights

    @property
    def _bundle(self) -> Tuple[object, int]:
        """Slot 0's hot-swap cell — the single-model server's one cell,
        kept as the canonical read for stats/info/harness code."""
        return self._bundles[0]

    @property
    def batcher(self) -> "_ServeBatcher":
        """Slot 0's batcher (the single-model server's only batcher)."""
        return self.batchers[0]

    def swap_params(self, named_or_params, version: int) -> None:
        """Swap the serving tree directly (in-process publisher hook,
        tests). `named_or_params` is either a (name, array) list (the
        WeightPublisher materialization) or a params pytree. Thread-safe
        by construction: the new (params, version) tuple is built fully,
        then published with one reference assignment — in-flight ticks
        keep the tuple they already read."""
        if isinstance(named_or_params, list):
            from dotaclient_tpu.transport.serialize import unflatten_params

            params = unflatten_params(named_or_params, self.params)
        else:
            params = named_or_params
        with self._swap_lock:
            self.params = params
            self.version = int(version)
            self.weight_swaps_total += 1
            self.model_swaps[0] += 1
            self._bundles[0] = (params, int(version))

    def swap_model(self, model_id: int, named_or_params, version: int) -> None:
        """Install a FROZEN tree into serve slot `model_id` (league
        opponents; the league sync loop and in-process harnesses call
        this). Slot 0 routes through swap_params so the live tree keeps
        its apply_weight_frame bookkeeping."""
        m = int(model_id)
        if m == 0:
            self.swap_params(named_or_params, version)
            return
        if not 0 < m < self.models:
            raise ValueError(
                f"model id {m} not resident (--serve.models {self.models})"
            )
        if isinstance(named_or_params, list):
            from dotaclient_tpu.transport.serialize import unflatten_params

            params = unflatten_params(named_or_params, self.params)
        else:
            params = named_or_params
        with self._swap_lock:
            self.model_swaps[m] += 1
            self._bundles[m] = (params, int(version))

    def poke(self) -> None:
        """Wake the weight-poll thread now (WeightPublisher on_published
        chaining): the swap lands at the next tick boundary instead of
        up to weight_poll_s later."""
        self._poke.set()

    def _poll_weights_loop(self) -> None:
        while not self._stop_poll.is_set():
            self._poke.wait(self.cfg.serve.weight_poll_s)
            self._poke.clear()
            if self._stop_poll.is_set():
                return
            try:
                frame = self.broker.poll_weights()
            except Exception as e:  # broker outage: keep serving the current tree
                _log.warning("serve: weight poll failed (%s); retrying", e)
                continue
            if frame is None:
                continue
            # Under the swap lock: apply_weight_frame reads self.version
            # for its staleness rules and mutates params/version — a
            # concurrent swap_params (the on_published hook) interleaving
            # with that read-modify-write could re-publish an older tree
            # over a newer one.
            with self._swap_lock:
                if apply_weight_frame(self, frame, "serve"):
                    # apply_weight_frame mutated params/version; publish
                    # them as one tuple for the tick reader.
                    self.weight_swaps_total += 1
                    self.model_swaps[0] += 1
                    self._bundles[0] = (self.params, self.version)

    def _league_sync_once(self) -> None:
        """One assignments poll against the league service: fetch the
        slot map, install any slot whose (name, version) changed. Plain
        stdlib HTTP (the discovery-client rule: the serve tier never
        imports dotaclient_tpu.league — the sync is a wire contract)."""
        import base64
        import urllib.request

        ep = str(self.cfg.serve.league_endpoint)
        timeout = max(1.0, float(self.cfg.serve.league_sync_s))
        with urllib.request.urlopen(
            f"http://{ep}/assignments", timeout=timeout
        ) as resp:
            body = json.loads(resp.read().decode("utf-8", "replace"))
        for slot_s, rec in (body.get("assignments") or {}).items():
            m = int(slot_s)
            if not 0 < m < self.models:
                continue  # a bigger league than this server holds slots for
            want = (str(rec.get("name", "")), int(rec.get("version", 0)))
            if self._synced.get(m) == want:
                continue
            with urllib.request.urlopen(
                f"http://{ep}/snapshot?name={want[0]}", timeout=timeout
            ) as resp:
                snap = json.loads(resp.read().decode("utf-8", "replace"))
            named = [
                (
                    str(name),
                    np.frombuffer(
                        base64.b64decode(arr["b64"]), dtype=np.dtype(arr["dtype"])
                    ).reshape(arr["shape"]),
                )
                for name, arr in (snap.get("params") or {}).items()
            ]
            self.swap_model(m, named, int(snap.get("version", want[1])))
            self._synced[m] = want
            self.league_syncs_total += 1
            _log.info("serve: league sync installed %s v%d into slot %d", want[0], want[1], m)

    def _league_sync_loop(self) -> None:
        while not self._stop_sync.wait(float(self.cfg.serve.league_sync_s)):
            try:
                self._league_sync_once()
            except Exception as e:  # league outage: keep serving current slots
                self.league_sync_errors_total += 1
                _log.warning("serve: league sync failed (%s); retrying", e)

    # ------------------------------------------------------------- serving

    def _zero_state(self):
        return jax.tree.map(np.asarray, P.initial_state(self.cfg.policy, (1,)))

    @staticmethod
    def _canon_obs(obs: F.Observation) -> F.Observation:
        """Upcast bf16 float leaves to f32 (exact) so ONE jit signature
        serves f32 and bf16 clients alike. f32 obs pass through
        untouched (same arrays, no copy)."""
        if np.dtype(obs.global_feats.dtype) == np.float32:
            return obs
        return obs._replace(
            global_feats=obs.global_feats.astype(np.float32),
            hero_feats=obs.hero_feats.astype(np.float32),
            unit_feats=obs.unit_feats.astype(np.float32),
        )

    async def _step_request(self, conn: _ClientConn, payload: bytes) -> None:
        try:
            req = W.decode_step_request(payload)
        except Exception as e:
            self.bad_requests_total += 1
            _log.warning("serve: bad step request: %s", e)
            # Echo the REAL client_key when the head parses (a
            # size-mismatched frame still carries it): the error must
            # route to the env that sent it, not to whichever env
            # happens to use key 0, and the sender must not sit out its
            # full reply timeout.
            import struct

            key = struct.unpack_from("<Q", payload)[0] if len(payload) >= 8 else 0
            await conn.send(
                W.R_STEP, W.encode_step_response(W.StepResponse(key, W.BAD_REQUEST))
            )
            return
        self.requests_total += 1
        self.model_requests[conn.model] += 1
        if req.replay:
            self.replayed_steps_total += 1
        if req.episode_start:
            state = self._zero_state()
            self.episode_resets_total += 1
        else:
            state = conn.carries.get(req.client_key)
            if state is None:
                self.unknown_client_total += 1
                await conn.send(
                    W.R_STEP,
                    W.encode_step_response(
                        W.StepResponse(req.client_key, W.UNKNOWN_CLIENT)
                    ),
                )
                return
        row, version, tick = await self.batchers[conn.model].step(
            state, self._canon_obs(req.obs), req.rng
        )
        if self.first_request_t is None:
            self.first_request_t = time.monotonic()
        new_state, action, logp, value, rng2 = row
        new_state = jax.tree.map(np.asarray, new_state)
        conn.carries[req.client_key] = new_state
        ep_step = 1 if req.episode_start else conn.steps.get(req.client_key, 0) + 1
        conn.steps[req.client_key] = ep_step
        carry = None
        if req.want_carry:
            carry = (np.asarray(new_state[0][0]), np.asarray(new_state[1][0]))
            if self._store is not None:
                # WRITE-AHEAD: the store entry lands BEFORE the reply
                # that vouches for this boundary — a kill can lose the
                # ack, never the entry (schedcheck HandoffModel's
                # handoff_after_ack mutant is this order inverted). A
                # store failure degrades, it never stops serving: the
                # session falls back to PR-10 abandon-on-failover.
                try:
                    # Store keys compose (client_key, model_id): a
                    # fleet's per-opponent sessions never alias in the
                    # shared store, and model 0 composes to the bare
                    # key — PR-13 store contents bit-for-bit.
                    await self._store.put(
                        W.compose_store_key(req.client_key, conn.model),
                        ep_step,
                        version,
                        carry[0],
                        carry[1],
                    )
                    self.handoff_writes_total += 1
                except Exception as e:
                    self.handoff_write_errors_total += 1
                    _log.warning(
                        "serve: carry handoff write failed for client %d (%s); "
                        "session degrades to abandon-on-failover",
                        req.client_key,
                        e,
                    )
        await conn.send(
            W.R_STEP,
            W.encode_step_response(
                W.StepResponse(
                    client_key=req.client_key,
                    status=W.OK,
                    version=version,
                    tick=tick,
                    rng=np.asarray(rng2),
                    action=np.asarray(
                        [action.type[0], action.move_x[0], action.move_y[0], action.target[0]],
                        np.int32,
                    ),
                    logp=float(np.asarray(logp)[0]),
                    value=float(np.asarray(value)[0]),
                    carry=carry,
                )
            ),
        )

    async def _resume_request(self, conn: _ClientConn, payload: bytes) -> None:
        """Session-continuity handshake: restore the client's boundary
        carry from the shared store and make it resident, so the replay
        steps that follow rebuild the mid-chunk carry bitwise. Spawned
        as a task like S_STEP — a slow store read must not head-of-line
        block the connection's OTHER envs' step frames (a fleet shares
        one connection, and post-kill every env resumes at once);
        per-key ordering is structural anyway: the client awaits the
        resume reply before sending its replay steps. Any refusal (no
        store, miss, stale, width or fingerprint mismatch) answers
        UNKNOWN_CLIENT: the client abandons, exactly the PR-10 path."""
        try:
            req = W.decode_resume_request(payload)
        except Exception as e:
            self.bad_requests_total += 1
            _log.warning("serve: bad resume request: %s", e)
            import struct

            key = struct.unpack_from("<Q", payload)[0] if len(payload) >= 8 else 0
            await conn.send(
                W.R_RESUME,
                W.encode_resume_response(W.ResumeResponse(key, W.UNKNOWN_CLIENT)),
            )
            return
        entry = None
        if self._store is not None:
            try:
                _, entry = await self._store.get(
                    W.compose_store_key(req.client_key, conn.model), req.boundary_step
                )
            except Exception as e:
                self.handoff_write_errors_total += 1
                _log.warning("serve: carry handoff read failed: %s", e)
        if entry is not None and entry.c.size != self.cfg.policy.lstm_hidden:
            _log.warning(
                "serve: store entry width %d != lstm_hidden %d — refusing resume "
                "(mixed-policy store?)",
                entry.c.size,
                self.cfg.policy.lstm_hidden,
            )
            entry = None
        if entry is not None:
            from dotaclient_tpu.serve.handoff import carry_fingerprint

            if carry_fingerprint(entry.c, entry.h) != req.carry_hash:
                # Step-only matching is not enough: episode boundaries
                # repeat the same step values across a client's
                # episodes, so a FAILED boundary write (store outage)
                # plus a previous episode's leftover entry could
                # exact-match on step and silently serve a
                # wrong-episode carry. The client holds the true
                # boundary carry — refuse anything whose bytes differ.
                _log.warning(
                    "serve: store entry for client %d boundary %d fails the "
                    "carry fingerprint — refusing resume (stale episode?)",
                    req.client_key,
                    req.boundary_step,
                )
                entry = None
        if entry is None:
            self.resume_misses_total += 1
            await conn.send(
                W.R_RESUME,
                W.encode_resume_response(
                    W.ResumeResponse(req.client_key, W.UNKNOWN_CLIENT)
                ),
            )
            return
        conn.carries[req.client_key] = (
            np.ascontiguousarray(entry.c, np.float32)[None],
            np.ascontiguousarray(entry.h, np.float32)[None],
        )
        conn.steps[req.client_key] = int(entry.episode_step)
        self.resumes_total += 1
        await conn.send(
            W.R_RESUME,
            W.encode_resume_response(
                W.ResumeResponse(
                    req.client_key, W.OK, int(entry.version), int(entry.episode_step)
                )
            ),
        )

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        conn = _ClientConn(writer)
        self._conns.add(conn)
        tasks: set = set()
        try:
            while True:
                mtype, payload = await W.read_frame(reader)
                if mtype == W.S_STEP:
                    # One task per request: a connection's envs step
                    # concurrently, and the batcher gathers them into
                    # one tick — handling serially would cap occupancy
                    # at 1 row per connection.
                    t = asyncio.ensure_future(self._step_request(conn, payload))
                    tasks.add(t)
                    t.add_done_callback(tasks.discard)
                elif mtype == W.S_RESUME:
                    t = asyncio.ensure_future(self._resume_request(conn, payload))
                    tasks.add(t)
                    t.add_done_callback(tasks.discard)
                elif mtype == W.S_STATS:
                    await conn.send(W.R_STATS, json.dumps(self.stats()).encode())
                elif mtype == W.S_INFO:
                    # Session establishment: an optional model id binds
                    # the CONNECTION to a frozen serve slot (empty
                    # payload = slot 0 = the legacy handshake,
                    # byte-identical). Handled inline before any step
                    # task can spawn — the client awaits R_INFO before
                    # sending steps, so the binding is race-free.
                    info = self.info()
                    try:
                        model = W.decode_info_request(payload)
                    except ValueError as e:
                        self.bad_requests_total += 1
                        info["model_error"] = str(e)
                        model = None
                    if model is not None:
                        if 0 <= model < self.models:
                            conn.model = model
                        else:
                            info["model_error"] = (
                                f"model {model} not resident "
                                f"(--serve.models {self.models})"
                            )
                    info["model"] = conn.model
                    await conn.send(W.R_INFO, json.dumps(info).encode())
                else:
                    raise ValueError(f"unknown message type {mtype:#x}")
        except (asyncio.IncompleteReadError, ConnectionResetError, BrokenPipeError):
            pass  # client went away; eviction below is the contract
        except asyncio.CancelledError:
            pass
        finally:
            self.evictions_total += len(conn.carries)
            self.model_evictions[conn.model] += len(conn.carries)
            conn.carries.clear()
            conn.steps.clear()
            self._conns.discard(conn)
            for t in tasks:
                t.cancel()
            writer.close()

    # ----------------------------------------------------------- lifecycle

    async def _main(self):
        drivers = [asyncio.ensure_future(b.run()) for b in self.batchers]
        self._stop_ev = asyncio.Event()
        self._server = await asyncio.start_server(self._handle, self.host, self.port)
        if self.port == 0:
            self.port = self._server.sockets[0].getsockname()[1]
        self._started.set()
        await self._stop_ev.wait()
        # Teardown order (the BrokerServer shutdown dance): stop
        # accepting, fail the batchers' pending futures, cancel handler
        # tasks, abort transports so close is immediate.
        self._server.close()
        for b in self.batchers:
            b.stop()
        me = asyncio.current_task()
        handlers = [t for t in asyncio.all_tasks() if t is not me]
        for t in handlers:
            t.cancel()
        for c in list(self._conns):
            c.writer.transport.abort()
        if handlers:
            await asyncio.gather(*handlers, return_exceptions=True)
        await self._server.wait_closed()
        for d in drivers:
            d.cancel()
        await asyncio.gather(*drivers, return_exceptions=True)
        if self._store is not None:
            try:
                await self._store.close()
            except Exception:
                pass

    def _warm(self) -> None:
        """Compile the tick signature before accepting traffic: a pad
        tick exercises the exact (params, state, obs, rng) shapes every
        real tick uses, so the first client request never pays the
        compile wall."""
        M = self.batcher.capacity
        state_b = jax.tree.map(
            lambda *xs: np.stack(xs), *([self.batcher._pad_state] * M)
        )
        obs_b = jax.tree.map(
            lambda *xs: np.stack(xs)[:, None], *([self.batcher._pad_obs] * M)
        )
        rng_b = np.stack([self.batcher._pad_rng] * M)
        out = self.batcher._step(self._bundle[0], state_b, obs_b, rng_b)
        jax.block_until_ready(out)

    def _run(self):
        loop = asyncio.new_event_loop()
        self._loop = loop
        asyncio.set_event_loop(loop)
        try:
            self._warm()
            loop.run_until_complete(self._main())
            pending = asyncio.all_tasks(loop)
            for t in pending:
                t.cancel()
            if pending:
                loop.run_until_complete(asyncio.gather(*pending, return_exceptions=True))
        except BaseException as e:
            self._boot_error = e
            self._started.set()
        finally:
            loop.close()

    def start(self) -> "InferenceServer":
        self._thread = threading.Thread(target=self._run, daemon=True, name="serve-server")
        self._thread.start()
        # Generous boot wait: _warm() compiles the full batched tick
        # signature before the listener comes up (flagship M=16 on a
        # cold CPU cache is tens of seconds).
        if not self._started.wait(300):
            raise RuntimeError("inference server failed to start (timeout)")
        boot_error = self._boot_error
        if boot_error is not None:
            raise RuntimeError(f"inference server failed to start: {boot_error}") from boot_error
        if self.broker is not None:
            self._poll_thread = threading.Thread(
                target=self._poll_weights_loop, daemon=True, name="serve-weights"
            )
            self._poll_thread.start()
        if self.models > 1 and str(self.cfg.serve.league_endpoint):
            self._sync_thread = threading.Thread(
                target=self._league_sync_loop, daemon=True, name="serve-league-sync"
            )
            self._sync_thread.start()
        if self.obs is not None:
            self.obs.serve_metrics([self.stats], health_provider=self._health)
        return self

    def stop(self) -> None:
        self._stop_poll.set()
        self._stop_sync.set()
        self._poke.set()
        loop = self._loop
        if loop is not None and not loop.is_closed():
            try:
                loop.call_soon_threadsafe(self._stop_ev.set)
            except RuntimeError:
                pass
        if self._thread:
            self._thread.join(timeout=10)
        if self._poll_thread:
            self._poll_thread.join(timeout=5)
        if self._sync_thread:
            self._sync_thread.join(timeout=5)
        if self.obs is not None:
            self.obs.close()

    # ------------------------------------------------------------- surface

    def stats(self) -> dict:
        # The actor_* batcher family aggregates across model slots (one
        # scrape surface, N tick streams); slot 0 alone at models=1 is
        # exactly the single-model stats.
        out = dict(self.batcher.stats())
        if self.models > 1:
            for b in self.batchers[1:]:
                for k, v in b.stats().items():
                    if isinstance(v, (int, float)):
                        out[k] = out.get(k, 0.0) + v
        out.update(
            {
                "serve_requests_total": float(self.requests_total),
                "serve_unknown_client_total": float(self.unknown_client_total),
                "serve_bad_requests_total": float(self.bad_requests_total),
                "serve_episode_resets_total": float(self.episode_resets_total),
                "serve_evictions_total": float(self.evictions_total),
                "serve_weight_swaps_total": float(self.weight_swaps_total),
                "serve_version": float(self._bundle[1]),
                "serve_clients_connected": float(len(list(self._conns))),
                "serve_carries_resident": float(
                    sum(len(c.carries) for c in list(self._conns))
                ),
                # Session continuity (serve/handoff.py; all zero with
                # --serve.handoff_endpoint unset).
                "serve_handoff_store_writes_total": float(self.handoff_writes_total),
                "serve_handoff_store_errors_total": float(self.handoff_write_errors_total),
                "serve_handoff_resumes_total": float(self.resumes_total),
                "serve_handoff_resume_misses_total": float(self.resume_misses_total),
                "serve_handoff_replayed_steps_total": float(self.replayed_steps_total),
            }
        )
        # The S_INFO load dict as registry-pinned gauges: the control
        # plane (and operators) scrape placement load off /metrics
        # instead of dialing S_INFO per probe.
        load = self.load()
        out.update(
            {
                "serve_load_clients": float(load["clients"]),
                "serve_load_occupancy": float(load["occupancy"]),
                "serve_load_pending": float(load["pending"]),
                "serve_load_capacity": float(load["capacity"]),
            }
        )
        # Multi-model tier (serve_model_* prefix family): per-slot
        # request/swap/eviction ledgers and the resident version, plus
        # league-sync counters. At --serve.models 1 only the resident
        # gauge and the two sync counters appear (all zero) — the
        # single-model scrape surface is otherwise unchanged.
        out["serve_models_resident"] = float(self.models)
        out["serve_league_syncs_total"] = float(self.league_syncs_total)
        out["serve_league_sync_errors_total"] = float(self.league_sync_errors_total)
        if self.models > 1:
            # Under the swap lock: the league sync thread mutates the
            # per-slot ledgers and bundle cells in place — a torn read
            # here would pair a slot's new version with its old counters.
            with self._swap_lock:
                for m in range(self.models):
                    out[f"serve_model_requests_total_{m}"] = float(self.model_requests[m])
                    out[f"serve_model_swaps_total_{m}"] = float(self.model_swaps[m])
                    out[f"serve_model_evictions_total_{m}"] = float(self.model_evictions[m])
                    out[f"serve_model_version_{m}"] = float(self._bundles[m][1])
        return out

    def load(self) -> dict:
        """The routing tier's placement signal (S_INFO "load"): live
        connection count plus mean tick occupancy derived from the
        actor_tick_rows_<k> histogram. Read on the serve loop thread
        (the info handler), same thread that writes the histogram."""
        hist = list(self.batcher._tick_rows)
        rows = sum(k * n for k, n in enumerate(hist))
        ticks = sum(hist[1:])  # k=0 never fires — a tick starts from a request
        occ = (rows / ticks / self.batcher.capacity) if ticks else 0.0
        return {
            "clients": len(list(self._conns)),
            "occupancy": round(occ, 4),
            "pending": self.batcher._queue.qsize(),
            "capacity": self.batcher.capacity,
        }

    def info(self) -> dict:
        """The S_INFO handshake body: what a client must agree with."""
        return {
            "role": "serve",
            "arch": self.cfg.policy.arch,
            "lstm_hidden": self.cfg.policy.lstm_hidden,
            "max_batch": self.cfg.serve.max_batch,
            "gather_window_s": self.cfg.serve.gather_window_s,
            "version": self._bundle[1],
            "models": self.models,
            "load": self.load(),
        }

    def _health(self) -> dict:
        return {
            "ok": True,
            "role": "serve",
            "version": self._bundle[1],
            "clients": len(list(self._conns)),
        }


def main(argv=None):
    from dotaclient_tpu.config import parse_config
    from dotaclient_tpu.obs import ObsRuntime
    from dotaclient_tpu.runtime.device import init_devices, use_compile_cache
    from dotaclient_tpu.transport.base import RetryPolicy
    from dotaclient_tpu.transport.base import connect as broker_connect

    logging.basicConfig(level=logging.INFO)
    cfg = parse_config(InferenceConfig(), argv)
    cache = use_compile_cache()
    init_devices(cfg.platform, "serve")
    broker = broker_connect(cfg.broker_url, retry=RetryPolicy.from_config(cfg.retry))
    if cfg.chaos.enabled:
        from dotaclient_tpu.chaos import wrap_broker

        broker = wrap_broker(broker, cfg.chaos)
    obs = ObsRuntime.create(cfg.obs, role="serve")
    server = InferenceServer(cfg, broker, obs_runtime=obs).start()
    _log.info(
        "serve ready: tick compiled, compile_cache=%s hits=%d misses=%d",
        cache.dir,
        cache.hits,
        cache.misses,
    )
    # The bench/orchestration contract: ONE parseable ready line with
    # the bound port (--serve.port 0 picks a free one).
    print(json.dumps({"serving": True, "port": server.port}), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


if __name__ == "__main__":
    main()
