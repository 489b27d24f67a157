"""Whole-step share of the chip's peak in training: the FLOPs a HEAT step
requires (:func:`step_flops`, counted from shapes) times steps per second in
the window, over the bf16 peak of the chips, in %."""


def step_flops(config: dict, batch: int) -> float:
    """FLOPs one HEAT step requires (recomputation not counted):

    * forward: B(n+1) K-wide dot products (2K each) and B(n+2) squared norms
      (2K each: user, positive, n negatives);
    * backward: twice the forward (a gradient for each operand of each
      product);
    * SGD: a multiply-add (2 per element) on the B user rows and the B(n+1)
      item-row gradients;
    * aggregation, when on: the history average of B x H rows (2K each),
      forward and backward (3x).
    """
    k, n = config["emb_dim"], config["num_negatives"]
    fwd = 2.0 * k * batch * (n + 1) + 2.0 * k * batch * (n + 2)
    sgd = 2.0 * k * (batch + batch * (n + 1))
    agg = 3 * 2.0 * k * batch * config["history_len"]
    return 3 * fwd + sgd + agg


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps") or not c.get("window_s"):
        return None
    flops = step_flops(ctx["config"], c["batch_size"])
    chips = ctx["device"]["count"]
    return 100.0 * flops * c["steps"] / c["window_s"] / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
