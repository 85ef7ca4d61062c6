"""Deployments as plain data, generated from a configuration file and a seed.

A configuration file (``bench/configs/<name>.json``) names a ``builder``
and the sizes it is run at; :func:`build` turns it into one of the plain
records below.  Nothing here imports the program: the reference reads
these records, and ``bench/adapter.py`` converts them into the program's
types.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Mapping, Optional, Tuple


@dataclass(frozen=True)
class Flavour:
    name: str
    cpu: float
    ram_gb: float
    energy_kwh: float       # centre of the monitored energy per window


@dataclass(frozen=True)
class Service:
    sid: str
    flavours: Tuple[Flavour, ...]   # in preference order

    def flavour(self, name: str) -> Flavour:
        for f in self.flavours:
            if f.name == name:
                return f
        raise KeyError(f"{self.sid}: no flavour {name!r}")


@dataclass(frozen=True)
class Link:
    src: str
    dst: str
    volume: float           # requests (or GB) per window at utilisation 1
    size_gb: float          # GB per request


@dataclass(frozen=True)
class Node:
    nid: str
    region: str
    cpu: float
    ram_gb: float
    cost: float             # per vCPU hour


@dataclass(frozen=True)
class Region:
    """Hourly carbon-intensity process of one grid region: a diurnal cycle
    plus AR(1) noise and optional renewable ramps (gCO2eq/kWh)."""

    base: float
    daily_amplitude: float
    trough_hour: float
    noise: float
    ramp_prob: float = 0.0
    ramp_depth: float = 0.0
    ramp_hours: int = 0


@dataclass(frozen=True)
class Microservices:
    """One application over a continuum of regions: the adaptive loop's
    deployment."""

    name: str
    services: Tuple[Service, ...]
    links: Tuple[Link, ...]
    nodes: Tuple[Node, ...]
    regions: Mapping[str, Region]
    telemetry: Mapping[str, float]  # swing, peak_hour, drift_per_h, noise,
                                    # samples, k_kwh_per_gb


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------


def _regions(spec: Mapping[str, Mapping]) -> Dict[str, Region]:
    return {name: Region(**p) for name, p in spec.items()}


def _boutique(cfg: Mapping, seed: int) -> Microservices:
    """The paper's Online Boutique (Table 1 services and flavours) on the
    Table 2 Europe nodes; sizes as published, so the seed only drives the
    traces."""
    sizes = cfg["flavour_sizes"]
    services = tuple(
        Service(sid, tuple(Flavour(name, *sizes[name], energy)
                           for name, energy in flavours))
        for sid, flavours in cfg["services"].items())
    links = tuple(Link(s, z, vol, size) for s, z, vol, size in cfg["traffic"])
    nodes = tuple(
        Node(nid, nid, cfg["node_cpu"], cfg["node_ram_gb"], n["cost"])
        for nid, n in cfg["nodes"].items())
    return Microservices(cfg["name"], services, links, nodes,
                         _regions(cfg["regions"]), cfg["telemetry"])


BUILDERS = {"boutique": _boutique}


def load_config(path: Path, overrides: Optional[Mapping] = None) -> dict:
    cfg = json.loads(Path(path).read_text())
    if overrides:
        cfg.update(overrides)
    return cfg


def build(cfg: Mapping, seed: int):
    """The deployment a configuration describes, generated from ``seed``."""
    builder = BUILDERS.get(cfg.get("builder"))
    if builder is None:
        raise ValueError(f"unknown builder {cfg.get('builder')!r} "
                         f"(known: {sorted(BUILDERS)})")
    return builder(cfg, seed)
