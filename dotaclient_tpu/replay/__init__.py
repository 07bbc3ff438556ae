"""Host-side prioritized experience replay between staging and the learner.

Where this sits relative to the reference RMQ pipe
--------------------------------------------------

The reference dotaclient pipe (agent → RabbitMQ → optimizer) is strictly
on-policy: the optimizer consumes whatever the queue holds and drops
rollouts whose model version has aged past its staleness bound. This
repo's `runtime/staging.py` reproduces that policy on the host — frames
older than `ppo.max_staleness` learner versions are discarded in
`_ingest`, before they cost any device time. Every dropped frame is
wasted actor work, and a learner that outpaces its actor fleet makes
it chronic: the version counter sprints ahead and mass-stales the
frames in flight.

This package converts that drop-on-stale policy into a tunable
freshness/efficiency tradeoff, following two pieces of related work:

- ACER (arxiv 1611.01224): off-policy reuse with *truncated importance
  weights* recovers the sample efficiency of replayed experience while
  bounding the variance of stale-ratio gradients. The loss-side half
  lives in `ops/ppo.py` — rows stamped with a positive behavior-policy
  staleness get their ratio truncated at `ppo.replay_rho_bar` before
  entering the clipped surrogate (exactly the plain PPO loss for
  fresh rows, so replay-off behavior is bit-identical).
- "Accelerating Distributed Deep RL by In-Network Experience Sampling"
  (arxiv 2110.13506): the sampling layer belongs in the *transport
  path*, not the learner. The reservoir therefore hangs off the
  broker-draining consumer thread in `runtime/staging.py` — the seam
  this repo already owns between the wire and the packed batch — not
  off the train loop.

Data plane (replay enabled):

    broker ─→ staging consumer thread
                ├─ fresh (within ppo.max_staleness) ──→ pending → packer
                ├─ near-stale (within replay.max_staleness)
                │        └──→ ReplayReservoir.offer  (would have been
                │             dropped_stale before)
                └─ too stale ──→ dropped_stale (as before)
    packer: each batch = (B - k) fresh + k = ratio·B reservoir samples,
            every row stamped with behavior-policy staleness
    learner: ships the batch as today; ops/ppo.py truncates the IS
            ratio on stale rows (ACER c̄ = ppo.replay_rho_bar)

The reservoir itself (`reservoir.py`) is single-writer by construction:
only the staging consumer thread calls `offer`/`sample`/`expire`, the
same discipline `tests/test_staging.py` asserts for the pending list;
`stats()` takes a lock and may be read from any thread. Entries are
version-bucketed so whole generations expire in O(1) bucket drops,
priorities follow the standard PER |TD-error| proxy for |advantage|
decayed by age, the total footprint is bounded by a byte budget with
lowest-priority-first eviction, and cold entries optionally spill to
zlib-compressed storage in place (still sampleable, ~3-5x smaller).

Default-off: with `LearnerConfig.replay.enabled=False` nothing here is
ever imported on the hot path and the staging/learner behavior — batch
contents, PPO loss, jit treedefs — is bit-identical to the pre-replay
code.
"""

from dotaclient_tpu.replay.reservoir import ReplayReservoir, td_error_priority

__all__ = ["ReplayReservoir", "td_error_priority"]
