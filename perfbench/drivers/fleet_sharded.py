"""Driver of the sharded fleet cell: a unit is one
``jaxsim.run_device_sharded`` call, which shards one lane's device axis
over the chips of a ``launch.mesh.make_sweep_mesh((k,))`` mesh (queue,
server and clock replicated), with its inputs sent afresh from the host
and its outputs fetched back.

Everything else is ``sim_sweep``'s, by inheritance: the streams, the
latency tiers, the drain budget, the warm-up, the check and the plain
reference (``perfbench/reference/cascade_sim.py``). The traffic holds one
lane; its streams go to the program without the lane axis, which is put
back on the outputs, so that ``unit``, ``check`` and ``compare`` read
them as a sweep's.

Configuration key read besides ``sim_sweep``'s: ``device_shards``, the
chips the fleet's device axis is split over. The outputs also carry
``exchanges_per_trip``: the program's count of the collectives one trip
of the sharded core issues (``jaxsim.stats``), or None where the program
keeps no such count.
"""
from __future__ import annotations

import numpy as np

from perfbench.drivers import sim_sweep


class Driver(sim_sweep.Driver):
    def setup(self):
        import jax
        from repro.launch.mesh import make_sweep_mesh
        k = int(self.cfg["device_shards"])
        if jax.device_count() < k:
            raise RuntimeError(
                f"the fleet's device axis is split over {k} chips; JAX "
                f"sees {jax.device_count()} device(s)")
        self.mesh = make_sweep_mesh((k,))
        super().setup()

    def _sweep(self, specs, streams, latency, slo):
        (spec,) = specs
        out = self.jaxsim.run_device_sharded(
            spec, {k: v[0] for k, v in streams.items()}, latency, slo,
            (self.server,), mesh=self.mesh)
        out = self.jax.tree.map(lambda x: np.asarray(x)[None],
                                self.jax.device_get(out))
        out["exchanges_per_trip"] = getattr(
            self.jaxsim.stats, "device_sharded_exchanges_per_trip", None)
        return out
