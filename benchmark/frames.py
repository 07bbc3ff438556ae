"""Rollout rows from a seed.

One general builder: the shapes come from the configuration's file, the
distribution of what a row holds from the traffic mix's `rows`. The rows
are plain numpy arrays with a leading row axis. The traffic mix's frame
builder (`wire/<name>.py`) turns them into wire frames; the plain
reference is given the arrays, never the frames: whatever the learner
decodes has to come out as these numbers.

Copied in spirit from `bench.py` `_make_frames` (sound, see PERF.md),
with three changes: all rows of a run are drawn at once (seconds, not
minutes, at 12,288 rows), actions are legal (ATTACK only where a target
exists, as `make_train_batch` has it), and the behaviour log-prob is
that of a near-uniform policy over the legal choices, which is what the
seeded weights give, so the PPO ratio sits near 1 and every head gets a
gradient.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

ACT_NOOP, ACT_MOVE, ACT_ATTACK, ACT_CAST = 0, 1, 2, 3


def make_rows(config: dict, rows_spec: dict, n_rows: int, seed: int) -> Dict[str, np.ndarray]:
    """`n_rows` distinct rollout chunks of the configuration's shapes."""
    f = config["features"]
    T = int(config["learner"]["seq_len"])
    H = int(config["policy"]["lstm_hidden"])
    bins = int(config["policy"]["n_move_bins"])
    U, UF = int(f["max_units"]), int(f["unit_features"])
    T1 = T + 1
    N = int(n_rows)
    r = np.random.Generator(np.random.PCG64(int(seed)))

    def randn(*shape):
        return r.standard_normal(shape, dtype=np.float32)

    unit_mask = r.random((N, T1, U), dtype=np.float32) < rows_spec["unit_mask_p"]
    target_mask = unit_mask & (
        r.random((N, T1, U), dtype=np.float32) < rows_spec["target_given_unit_p"]
    )
    can_attack = target_mask.any(-1)  # [N, T1]
    action_mask = np.zeros((N, T1, int(f["n_action_types"])), bool)
    action_mask[..., ACT_NOOP] = True
    action_mask[..., ACT_MOVE] = True
    action_mask[..., ACT_ATTACK] = can_attack

    atype = r.integers(0, 2, size=(N, T)).astype(np.int32)
    attack = can_attack[:, :T] & (r.random((N, T), dtype=np.float32) < rows_spec["attack_p"])
    atype = np.where(attack, ACT_ATTACK, atype).astype(np.int32)
    first_valid = np.argmax(target_mask[:, :T], axis=-1).astype(np.int32)
    target = np.where(can_attack[:, :T], first_valid, 0).astype(np.int32)

    # Log-prob of the action under a uniform policy over the legal choices.
    n_types = action_mask[:, :T].sum(-1).astype(np.float32)
    n_targets = np.maximum(target_mask[:, :T].sum(-1), 1).astype(np.float32)
    logp = -np.log(n_types)
    logp = logp - (atype == ACT_MOVE) * (2.0 * math.log(bins))
    logp = logp - (atype == ACT_ATTACK) * np.log(n_targets)
    logp = (logp + rows_spec["logp_noise"] * randn(N, T)).astype(np.float32)

    dones = np.zeros((N, T), np.float32)
    dones[r.random(N) < rows_spec["done_last_p"], -1] = 1.0

    return {
        "global_feats": randn(N, T1, int(f["global_features"])),
        "hero_feats": randn(N, T1, int(f["hero_features"])),
        "unit_feats": randn(N, T1, U, UF),
        "unit_mask": unit_mask,
        "target_mask": target_mask,
        "action_mask": action_mask,
        "type": atype,
        "move_x": r.integers(0, bins, size=(N, T)).astype(np.int32),
        "move_y": r.integers(0, bins, size=(N, T)).astype(np.int32),
        "target": target,
        "behavior_logp": logp,
        "behavior_value": (rows_spec["value_scale"] * randn(N, T)).astype(np.float32),
        "rewards": (rows_spec["reward_scale"] * randn(N, T)).astype(np.float32),
        "dones": dones,
        "c0": (rows_spec["carry_scale"] * randn(N, H)).astype(np.float32),
        "h0": np.tanh(rows_spec["carry_scale"] * randn(N, H)).astype(np.float32),
    }


def rows_slice(rows: Dict[str, np.ndarray], start: int, stop: int) -> Dict[str, np.ndarray]:
    return {k: v[start:stop] for k, v in rows.items()}
