"""Share of the device's busy time in the window spent in collective
operations (the W-delta all-reduce, the stats' psums): ``collective_s``
over ``busy_s``, each averaged over the cell's chips by
``bench/trace_reduce.py``. None where the run was not traced or the
reduction has no collective time."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr.get("collective_s") is None or not tr.get("busy_s"):
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]
