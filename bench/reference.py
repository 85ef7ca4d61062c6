"""The guarantees a committed placement has to keep, in plain Python.

It imports nothing of the program.  ``bench/refloop.py`` works out what
each tick has to decide; this module holds what any placement has to
satisfy and how two answers are compared:

* feasibility: every service placed, on a flavour it offers, and no node
  loaded past its CPU or RAM;
* the switch charge: a service that changes node is a migration, one
  that changes only flavour a restart;
* the gap between a number and the reference's.
"""
from __future__ import annotations

from typing import Dict, Mapping, Tuple

import numpy as np

Assignment = Mapping[str, Tuple[str, str]]

CAPACITY_EPS = 1e-9


def violations(services: Mapping, nodes: Mapping, assign: Assignment) -> int:
    """Broken guarantees of one application's placement: services left
    out, unknown flavours or nodes, nodes past capacity."""
    bad = sum(1 for sid in services if sid not in assign)
    cpu: Dict[str, float] = {}
    ram: Dict[str, float] = {}
    for sid, (f, n) in assign.items():
        svc = services.get(sid)
        if svc is None or n not in nodes \
                or f not in {fl.name for fl in svc.flavours}:
            bad += 1
            continue
        fl = svc.flavour(f)
        cpu[n] = cpu.get(n, 0.0) + fl.cpu
        ram[n] = ram.get(n, 0.0) + fl.ram_gb
    for n in cpu:
        if cpu[n] > nodes[n].cpu + CAPACITY_EPS \
                or ram[n] > nodes[n].ram_gb + CAPACITY_EPS:
            bad += 1
    return bad


def switch_charge(old: Assignment, new: Assignment) -> Tuple[int, int]:
    """(migrations, restarts) of replacing ``old`` by ``new``."""
    moved = sum(1 for s, (_, n) in new.items()
                if s not in old or old[s][1] != n)
    moved += sum(1 for s in old if s not in new)
    flapped = sum(1 for s, (f, n) in new.items()
                  if s in old and old[s][1] == n and old[s][0] != f)
    return moved, flapped


def rel_gap(value: float, ref: float, scale: float = None) -> float:
    """|value - ref| over ``scale`` (default |ref|); inf where the value is
    not a finite number."""
    if not np.isfinite(value):
        return float("inf")
    scale = abs(ref) if scale is None else abs(scale)
    return abs(value - ref) / max(scale, 1e-300)
