"""Share of the device's busy time in the window spent rebuilding the
counts D and W: the program ``jit_update_counts``
(``core/esca.update_counts``), found by its module name."""

from bench.module_share import share

MODULES = ("jit_update_counts",)


def read(ctx):
    return share(ctx, MODULES)
