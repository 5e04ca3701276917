"""``device_ns_per_config_step`` (ns): device busy time (leaf operations),
summed over the devices, per executed configuration step (the sum over the traced
sweeps' configurations of ``steps_run``). It reads the rollout loop and
kernel's speed on the work actually executed, which a change made only
for speed leaves bit-identical; moves ``configs_per_s``."""


def read(record: dict) -> float | None:
    tr = record.get("trace")
    steps = sum(int(s["res"].steps_run.astype("int64").sum())
                for s in record["sweeps"])
    if not tr or tr["busy_ns_total"] <= 0 or steps <= 0:
        return None
    return tr["busy_ns_total"] / steps
