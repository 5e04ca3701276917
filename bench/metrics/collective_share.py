"""``collective_share`` (%): of the device busy time (leaf operations,
summed over the chips), the share spent in operations that exchange data
between chips, told by their opcode (XLA's ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``; ``trace.COLLECTIVE_OPS``). In the sharded sweep that is
the exit vote's ``psum`` after every rollout block, ``%psum.<n> = s32[]
all-reduce``. One chip exchanges nothing, so there it reads nothing.
Moves ``configs_per_s``."""


def read(record: dict) -> float | None:
    tr = record.get("trace")
    if not tr or tr["n_devices"] < 2 or tr["busy_ns_total"] <= 0:
        return None
    return 100.0 * tr["collective_ns_total"] / tr["busy_ns_total"]
