"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's operation intervals) / window. Serves
``device_idle.<cell kind>``: the same quantity in cells that report
different end-to-end rates."""
from perfbench.core import trace


def read(ctx):
    if not trace.planes(ctx.trace):
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s)
