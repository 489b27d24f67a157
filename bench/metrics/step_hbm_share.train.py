"""Share of peak HBM bandwidth that the least bytes of a training step
(:func:`step_bytes`) take at the window's step time, in %.  The count
depends on the batch law and the configuration, not on how the program
implements the step, so no correct implementation reads over 100%."""

from bench.traffic.tables import row_bytes


def step_bytes(config: dict, users: float, positives: float) -> float:
    """Each distinct table row the step touches, read once and written once
    (the distinct users, the distinct positives and the tile's rows written
    back), and the dataset entries the batch reads (the drawn column and
    the fallback column, one int32 each per user)."""
    rows = users + positives + config["tile_size"]
    return 2.0 * rows * row_bytes(config) + 2 * 4.0 * users


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps") or "distinct_users" not in c:
        return None
    step_s = c["window_s"] / c["steps"]
    nbytes = step_bytes(ctx["config"], c["distinct_users"],
                        c["distinct_positives"])
    chips = ctx["device"]["count"]
    return 100.0 * nbytes / step_s / (chips * ctx["peaks"]["hbm_bytes_per_s"])
