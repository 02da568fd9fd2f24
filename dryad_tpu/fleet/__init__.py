"""dryad_tpu.fleet — a replicated serving pool behind one router.

The serve stack (dryad_tpu/serve) is one process: one crash, stall, or
hot-swap pause takes down all traffic.  This package is the
shared-nothing layer above it — N serve subprocesses supervised like
training runs (crash/hang detection, budgeted respawn with backoff, an
append-only journal: the resilience subsystem's machinery pointed at
processes instead of device faults), fronted by a thin stdlib router
(health-aware routing, one retry on a different replica, priority-classed
load shedding, per-model admission caps) with zero-drop rolling model
pushes (drain at the pinned version, then swap, replica by replica),
one aggregated ``/metrics``/``/healthz`` scrape for the whole pool, and
(r22) an SLO-driven capacity loop (``CapacityController``) that adds a
replica on sustained p99 breach or admission saturation and drains one
back out on sustained headroom, inside declared min/max bounds.

The package is host-side and jax-free by lint (the same contract as
``dryad_tpu/obs``): replicas own the devices; the fleet owns processes
and sockets.  Entry points::

    from dryad_tpu.fleet import FleetSupervisor, FleetRouter, serve_argv
    sup = FleetSupervisor(
        lambda i, pf: serve_argv(["m.dryad"], pf, backend="auto"),
        n_replicas=2, make_env=lambda i: serve_env(i, "auto"),
        journal="fleet.jsonl").start()
    router = FleetRouter(sup, port=8000).start()

or ``python -m dryad_tpu fleet --model m.dryad --replicas 2 --port 8000``.
"""

from dryad_tpu.fleet.autoscale import CapacityController
from dryad_tpu.fleet.replica import (ReplicaProcess, ReplicaStartupError,
                                     serve_argv, serve_env)
from dryad_tpu.fleet.router import (FleetRouter, make_fleet_router,
                                    relabel_exposition)
from dryad_tpu.fleet.supervisor import FleetSupervisor, ReplicaSlot

__all__ = [
    "CapacityController", "FleetRouter", "FleetSupervisor",
    "ReplicaProcess", "ReplicaSlot", "ReplicaStartupError",
    "make_fleet_router", "relabel_exposition", "serve_argv", "serve_env",
]
