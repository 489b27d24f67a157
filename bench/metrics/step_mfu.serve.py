"""Whole serving window's share of the chip's peak: the FLOPs of scoring
every request against the whole catalog (2 I K each), times requests per
second in the window, over the bf16 peak, in %."""


def read(ctx):
    c, config = ctx["counters"], ctx["config"]
    if not c.get("requests") or not c.get("window_s"):
        return None
    flops = 2.0 * config["num_items"] * config["emb_dim"] * c["requests"]
    chips = ctx["device"]["count"]
    return 100.0 * flops / c["window_s"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
