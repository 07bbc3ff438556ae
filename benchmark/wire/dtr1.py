"""The frame builder `dtr1`: one DTR1 frame per row, through the
program's own actor-side encoder (`serialize_rollout`), which is how any
actor reaches the learner; and the two header fields a generator touches.
A traffic mix names its frame builder (`"frames": {"module": "dtr1"}`).
"""

from __future__ import annotations

import struct
from typing import Dict, List

import numpy as np


def serialize_rows(rows: Dict[str, np.ndarray], spec: dict) -> List[bytes]:
    """One frame per row, at version 0. `spec` is the traffic mix's
    `frames` entry; this builder has no parameters of its own."""
    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.ops.action_dist import Action
    from dotaclient_tpu.transport.serialize import Rollout, serialize_rollout

    n = rows["rewards"].shape[0]
    out = []
    for i in range(n):
        out.append(
            serialize_rollout(
                Rollout(
                    obs=F.Observation(
                        global_feats=rows["global_feats"][i],
                        hero_feats=rows["hero_feats"][i],
                        unit_feats=rows["unit_feats"][i],
                        unit_mask=rows["unit_mask"][i],
                        target_mask=rows["target_mask"][i],
                        action_mask=rows["action_mask"][i],
                    ),
                    actions=Action(
                        type=rows["type"][i],
                        move_x=rows["move_x"][i],
                        move_y=rows["move_y"][i],
                        target=rows["target"][i],
                    ),
                    behavior_logp=rows["behavior_logp"][i],
                    behavior_value=rows["behavior_value"][i],
                    rewards=rows["rewards"][i],
                    dones=rows["dones"][i],
                    initial_state=(rows["c0"][i], rows["h0"][i]),
                    version=0,
                    actor_id=i,
                )
            )
        )
    return out


_VERSION = struct.Struct("<I")


def stamp_version(frame: bytes, version: int) -> bytes:
    """The frame with `version` in its header: a u32 at byte 4 of the
    rollout header, as of the weight header it was read from."""
    return frame[:4] + _VERSION.pack(version) + frame[8:]


def weights_version(frame: bytes) -> int:
    """The version a weight frame carries (u32 at byte 4, DTW1 and DTW2)."""
    return _VERSION.unpack_from(frame, 4)[0]
