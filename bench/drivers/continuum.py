"""What the two continuum drivers share: runtimes over generated traces."""
from __future__ import annotations

from typing import Mapping

import numpy as np

from .. import adapter
from ..deployment import Microservices
from ..refloop import ReferenceLoop
from ..signals import Carbon, Telemetry, carbon_series


class Continuum:
    """A deployment's carbon and telemetry traces, ``hours`` long, and the
    ``ContinuumRuntime`` objects that read them, configured by the traffic
    mix.  The traces come from ``seed``; the forecast ensembles from
    ``seed`` and a ``stream`` per runtime."""

    def __init__(self, dep: Microservices, mix: Mapping, seed: int,
                 hours: int):
        self.dep, self.mix, self.seed = dep, mix, seed
        self.series = carbon_series(dep.regions, hours, seed)
        self.tel = Telemetry(dep, hours, seed)
        self.node_ids = [n.nid for n in dep.nodes]
        self.node_regions = [n.region for n in dep.nodes]
        self.services = {s.sid: s for s in dep.services}
        self.nodes = {n.nid: n for n in dep.nodes}
        self.app, self.infra = adapter.app_and_infra(dep)
        self.feed = adapter.TelemetryFeed(self.tel)

    def carbon(self, stream: int = 0) -> Carbon:
        return Carbon(self.series, self.seed, stream)

    def runtime(self, stream: int = 0, obs=None):
        """A runtime configured as the mix states: the loop's settings,
        the constraint pass and the planner's objective."""
        from repro.continuum import (ContinuumRuntime, RuntimeConfig,
                                     WhatIfPlanner)
        from repro.core.energy import EnergyMixGatherer
        from repro.core.kb import KBEnricher
        from repro.core.pipeline import GreenConstraintPipeline
        from repro.core.ranker import ConstraintRanker
        from repro.core.scheduler import GreenScheduler, SchedulerConfig

        mix = self.mix
        return ContinuumRuntime(
            self.app, self.infra, self.carbon(stream), self.feed,
            config=RuntimeConfig(
                horizon_h=int(mix["horizon_h"]),
                scenarios=int(mix["scenarios"]),
                hysteresis_g=float(mix["hysteresis_g"]),
                migration_g=float(mix["migration_g"]),
                restart_g=float(mix["restart_g"])),
            pipeline=GreenConstraintPipeline(
                alpha=float(mix["alpha"]),
                gatherer=EnergyMixGatherer(window=int(mix["ci_window"])),
                enricher=KBEnricher(decay=float(mix["kb_decay"]),
                                    forget=float(mix["kb_forget"]),
                                    valid=float(mix["kb_valid"])),
                ranker=ConstraintRanker(
                    discard_below=float(mix["discard_below"]))),
            planner=WhatIfPlanner(GreenScheduler(SchedulerConfig(
                money_weight=float(mix["money_weight"]),
                pref_weight=float(mix["pref_weight"]),
                emission_weight=float(mix["emission_weight"]),
                green_penalty=float(mix["green_penalty"]),
                local_search_rounds=int(mix["local_search_rounds"])))),
            obs=obs)

    def reference(self, stream: int, start: int, dtype=np.float64):
        """The plain reference of a runtime on ``stream`` from ``start``."""
        return ReferenceLoop(self.dep, self.mix, self.series,
                             self.carbon(stream), self.tel, start, dtype)
