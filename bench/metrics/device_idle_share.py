"""``device_idle_share`` (%): the share of the traced window in which no
leaf operation ran on the device (a loop that waits between the
operations of its body is idle), the mean over the devices; moves
``configs_per_s``."""


def read(record: dict) -> float | None:
    tr = record.get("trace")
    if not tr or tr["n_devices"] == 0 or tr["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
