"""Device time of one serving call (scoring and top-k over the catalog):
device busy time in the traced window over the device calls made in it,
in ms."""


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or not c.get("device_calls"):
        return None
    return 1e3 * tr.busy_s / c["device_calls"]
