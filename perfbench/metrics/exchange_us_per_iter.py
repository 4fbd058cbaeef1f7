"""Device time per trip of the collectives of the device-sharded event
core (the ``psum``s and ``pmin``s of ``jaxsim._device_engine``): the
union of the intervals of the collective ops in the traced window, over
the trips of the loop that ran most (``trace.loop_trips``), mean over
the device planes, in microseconds. Serves
``exchange_us_per_iter.<cell kind>``.

Collectives are found by their HLO op names, which ``trace.load``
keeps. The compiler names one it makes after its opcode
(``all-reduce*``, ``all-gather*``, ``reduce-scatter*``,
``collective-permute*``, ``all-to-all*``, with the ``-start`` and
``-done`` halves of an asynchronous one; ``all-reduce.13`` is three of
the program's psums combined), and one that JAX emits alone after JAX's
collective (``pmin.56``, ``psum.294``), as a v5e compile of the sharded
core shows. Reads None where a plane has no collective op, or more
collectives a trip than the program's own count (``exchanges_per_trip``
in the unit's outputs, from its jaxpr, one per array; the compiler may
combine several into one all-reduce, so the trace may count fewer):
either means the names are not what this reader assumes. None too where
the program keeps no such count.

No roofline share goes with it: the all-reduces move O(G + MAX_POP)
words a trip, so their time is the latency of the exchange over the
chips' interconnect, not bandwidth.
"""
from perfbench.core import trace

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all",
               "psum", "pmin", "pmax", "ppermute", "all_gather",
               "all_to_all", "reduce_scatter")


def read(ctx):
    counts = [u["out"].get("exchanges_per_trip") for u in ctx.units]
    trips = trace.loop_trips(ctx.trace)
    if None in counts or not counts or not trips:
        return None
    lo, hi = ctx.trace.window
    per_trip = []
    for p in trace.planes(ctx.trace):
        ops = [(max(a, lo), min(b, hi), n)
               for a, b, n in ctx.trace.ops.get(p, ())
               if b > lo and a < hi and n.startswith(COLLECTIVES)]
        issued = sum(1 for _, _, n in ops
                     if not n.split(".", 1)[0].endswith("-done"))
        if not ops or round(issued / trips) > min(counts):
            return None
        per_trip.append(sum(b - a for a, b in trace.union(ops)) / trips)
    return sum(per_trip) / len(per_trip) * 1e-3 if per_trip else None
