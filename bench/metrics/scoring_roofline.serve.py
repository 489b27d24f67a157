"""Roofline share of the scoring and top-k call: the least time a call
could take (:func:`least_s`, with B the mean requests per call) over the
measured device time per call, in %."""

from bench.traffic.tables import row_bytes


def call_flops(config: dict, requests: float) -> float:
    """2 I K FLOPs per request scored against the whole catalog."""
    return 2.0 * config["num_items"] * config["emb_dim"] * requests


def call_bytes(config: dict, requests: float) -> float:
    """The item table's serving bytes (payload and scales, no residual) and
    the users' rows."""
    return (config["num_items"] + requests) * row_bytes(config)


def least_s(config: dict, peaks: dict, requests: float):
    """(least seconds of one call, the bound that sets it: ``bytes`` or
    ``flops``)."""
    t_flops = call_flops(config, requests) / peaks["bf16_flops_per_s"]
    t_bytes = call_bytes(config, requests) / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("device_calls") or tr.busy_s <= 0:
        return None
    least, _ = least_s(ctx["config"], ctx["peaks"],
                       c["requests"] / c["device_calls"])
    return 100.0 * least / (tr.busy_s / c["device_calls"])
