"""The generated inputs, in the program's types.

This is the only module of the yardstick that builds program objects
from the plain records of ``bench/deployment.py`` and the arrays of
``bench/signals.py``; the reference never sees them.
"""
from __future__ import annotations

from typing import Dict

from .deployment import Microservices
from .signals import Telemetry


def _flavours(svc):
    from repro.core.types import Flavour, FlavourRequirements

    return tuple(Flavour(f.name, FlavourRequirements(cpu=f.cpu,
                                                     ram_gb=f.ram_gb))
                 for f in svc.flavours)


def _services(services):
    from repro.core.types import Service

    return tuple(Service(s.sid, must_deploy=True, flavours=_flavours(s),
                         flavours_order=tuple(f.name for f in s.flavours))
                 for s in services)


def _infra(name: str, nodes):
    from repro.core.types import Infrastructure, Node, NodeCapabilities

    return Infrastructure(name, tuple(
        Node(n.nid, region=n.region, cost_per_cpu_hour=n.cost,
             capabilities=NodeCapabilities(cpu=n.cpu, ram_gb=n.ram_gb))
        for n in nodes))


def app_and_infra(dep: Microservices):
    """Application and Infrastructure of a continuum deployment; the nodes
    carry regions and no carbon (the runtime reads the trace)."""
    from repro.core.types import Application, CommunicationLink

    links = tuple(CommunicationLink(ln.src, ln.dst) for ln in dep.links)
    app = Application(dep.name, _services(dep.services), links)
    return app, _infra(dep.name, dep.nodes)


class TelemetryFeed:
    """The monitoring feed the runtime reads: ``monitoring(t)`` builds the
    hour's samples from the pre-generated arrays."""

    def __init__(self, telemetry: Telemetry):
        self.tel = telemetry

    def monitoring(self, t: int):
        from repro.core.types import EnergySample, MonitoringData, TrafficSample

        tel = self.tel
        energy = tuple(
            EnergySample(s, f, v, t=t)
            for (s, f), row in zip(tel.cells, tel.energy[t].tolist())
            for v in row)
        traffic = tuple(
            TrafficSample(source=s, source_flavour=f, target=z,
                          request_volume=v, request_size_gb=size, t=t)
            for (s, f, z), row, size in zip(
                tel.edges, tel.volume[t].tolist(), tel.size_gb.tolist())
            for v in row)
        return MonitoringData(energy=energy, traffic=traffic)


def assignment(placements) -> Dict[str, tuple]:
    """``{service: (flavour, node)}`` of a program DeploymentPlan's
    placements."""
    return {p.service: (p.flavour, p.node) for p in placements}
