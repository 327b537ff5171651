"""The whole training iteration's share of the chip's peak: tokens per
second per chip over the most tokens per second the chip's peaks allow for
the dense collapsed-Gibbs update (``roofline.token_work``)."""

from bench import roofline


def read(ctx):
    if ctx.get("peak") is None:
        return None
    best = roofline.roofline_tokens_per_s(ctx["n_topics"], ctx["peak"])
    return 100.0 * ctx["tokens_per_s"] / best
