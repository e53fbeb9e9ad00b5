"""The paper's technique as a first-class DISTRIBUTED feature: federated
async boosting compiled into a single pjit/shard_map step over a device
mesh — adaptive interval, buffers, compensation and the sync collective all
inside jit (DESIGN.md §3-4).

One federated client per device: on a CPU host, ask XLA for virtual
devices to get a multi-client mesh (the flag must be set before JAX
starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/fed_mesh_demo.py
"""
import dataclasses

import jax

from repro.configs.paper_fedboost import FedBoostConfig
from repro.sim.scenarios import DOMAINS
from repro.core import fed_mesh
from repro.data import make_domain_data
from repro.models.weak import stump_thresholds

K = jax.device_count()   # one federated client per device on the mesh
dom = dataclasses.replace(DOMAINS["edge_vision"], n_clients=K)
x, y, xv, yv = fed_mesh.pack_clients(make_domain_data(dom, seed=0), K)

mesh = fed_mesh.client_mesh(jax.devices())
cfg = FedBoostConfig(n_clients=K)
thresholds = stump_thresholds(x.reshape(-1, x.shape[-1]))
step = fed_mesh.make_fed_boost_step(cfg, mesh, "clients", thresholds)
state = fed_mesh.init_state(cfg, K, x.shape[1], xv.shape[1], buffer_cap=8,
                            ens_cap=2048, key=jax.random.key(0))
state, x, y, xv, yv = fed_mesh.place(mesh, "clients", state, x, y, xv, yv)

jstep = jax.jit(step, donate_argnums=0)
print(f"{K} clients on a {mesh.devices.shape} mesh; "
      f"sync = all_gather of the stump buffers over the client axis\n")
print(f"{'round':>6} {'interval':>9} {'syncs':>6} {'ensemble':>9} {'val_err':>8}")
for r in range(48):
    state = jstep(state, x, y, xv, yv)
    if (r + 1) % 8 == 0:
        print(f"{r+1:>6} {float(state.interval):>9.1f} "
              f"{int(state.sync_count):>6} {int(state.ens_count):>9} "
              f"{float(state.prev_err):>8.3f}")
print("\nThe interval widened in-graph (lax.cond-gated collective) while the"
      "\nensemble error fell — the paper's scheduling on SPMD hardware.")
