"""Share of the traced serving window in which no operation ran on the
device (profiler trace: 1 - union of op intervals / window), in %."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
