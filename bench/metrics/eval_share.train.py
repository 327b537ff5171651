"""Share of the device's busy time in the window spent in the LLPT
evaluation's programs (``core/llpt.py``), found by their module names."""

EVAL_MODULES = ("jit_token_ll", "jit_reduce_ll", "jit__colsum_f32")


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    found = [s for name, s in tr["module_s"].items() if name in EVAL_MODULES]
    if not found:
        return None
    return 100.0 * sum(found) / tr["busy_s"]
