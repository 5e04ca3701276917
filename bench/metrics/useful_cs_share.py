"""``useful_cs_share`` (%): of the critical sections the window's sweeps
simulated, the share that was asked for, ``100 x sum(min(completed,
target_cs)) / sum(completed)``. The blocked rollout runs every
configuration of a chunk until the slowest one reaches ``target_cs``;
the rest is work no user asked for. Moves ``configs_per_s``."""

import numpy as np


def read(record: dict) -> float | None:
    tc = int(record["target_cs"])
    done = np.concatenate([np.asarray(s["res"].completed, np.int64)
                           for s in record["sweeps"]])
    total = int(done.sum())
    if total <= 0:
        return None
    return 100.0 * int(np.minimum(done, tc).sum()) / total
