"""Requests per device call of the serving queue (``BatchingRecommender
.stats``: change of ``requests_served`` over change of ``device_calls``
across the window)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("device_calls"):
        return None
    return c["requests"] / c["device_calls"]
