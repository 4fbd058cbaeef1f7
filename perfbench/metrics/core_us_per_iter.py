"""Device time per trip of the sim core's event loop (the lane-batched
``lax.while_loop`` of ``jaxsim._run_core_lanes``, one trip per event or
window boundary), read from the trace as the period of the loop body's
operations (``trace.loop_period_s``). Serves ``core_us_per_iter.<cell
kind>``: the same quantity in cells that report different end-to-end
rates."""
from perfbench.core import trace


def read(ctx):
    period = trace.loop_period_s(ctx.trace)
    return None if period is None else period * 1e6
