"""Host time of one sweep call: the wall time of each ``bench.unit``
span minus the device time of the sim core's program that it waited
for (one run per span): ``_prepare``, the transfers of the streams,
dispatch, the fetch of the outputs and ``_finalize``, as the mean over
the traced units."""
from perfbench.core import trace

PROGRAM = "jit__unknown"


def read(ctx):
    units = trace.spans(ctx.trace, "bench.unit")
    if not units:
        return None
    device = trace.module_s_by_span(ctx.trace, PROGRAM, units)
    if device is None:
        return None
    host = [(b - a) * 1e-9 - d for (a, b), d in zip(units, device)]
    return sum(host) / len(host) * 1e3
