"""``padded_share`` (%): of the row steps the traced sweep's device calls
ran, the share spent on padding rows, ``100 x (1 - sum(steps_run) /
row_steps)``. A device call pads its rows up to the compiled shape of the
chunk ladder with copies of the last row, which simulate work no user
asked for. ``row_steps`` is the program's own count (``StreamResult``:
every row a device call simulated, padding included, times the steps it
executed); a program without it reads nothing. Moves ``configs_per_s``."""


def read(record: dict) -> float | None:
    steps = row_steps = 0
    for s in record["sweeps"]:
        counted = getattr(s["res"], "row_steps", None)
        if counted is None:
            return None
        row_steps += int(counted)
        steps += int(s["res"].steps_run.astype("int64").sum())
    if row_steps <= 0:
        return None
    return 100.0 * (1.0 - steps / row_steps)
