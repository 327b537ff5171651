"""The share of the device's busy time in the window spent inside the
named programs (XLA modules) of a traced run, for the per-layer readers."""


def share(ctx, modules) -> float | None:
    """100 · (device seconds inside ``modules``) / busy seconds, or None
    when the run was not traced or none of the programs ran in it."""
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    found = [s for name, s in tr["module_s"].items() if name in modules]
    if not found:
        return None
    return 100.0 * sum(found) / tr["busy_s"]
