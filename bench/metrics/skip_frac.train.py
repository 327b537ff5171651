"""Share of tokens that passed the three-branch skip test, as the
``frac_skipped`` counter in ``fit``'s history reads it at the window's
evaluated iterations."""


def read(ctx):
    vals = [s["frac_skipped"] for s in ctx.get("stats", [])
            if "frac_skipped" in s]
    if not vals:
        return None
    return 100.0 * sum(vals) / len(vals)
