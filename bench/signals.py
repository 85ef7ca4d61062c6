"""Carbon and telemetry traces, generated from the seed as plain arrays.

:class:`Carbon` is the grid signal: hourly series per region, the
persistence forecast the operator plans on and the forecast ensemble the
what-if planner prices.  It has the methods the program's continuum
runtime reads without an oracle (``history_signal``,
``forecast_signal``, ``scenario_matrix``, ``now``), so the runtime takes
it as its carbon trace.  :class:`Telemetry` holds every monitoring sample of
the run; ``bench/adapter.py`` hands them to the program tick by tick and
the reference reads the same arrays.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

from .deployment import Microservices, Region

CI_FLOOR = 5.0          # gCO2eq/kWh: no grid is ever carbon-free
SCENARIO_SIGMA = 0.10   # lognormal spread of the forecast ensemble
HISTORY_H = 7 * 24      # hours of history the grid service returns


def carbon_series(regions: Mapping[str, Region], hours: int,
                  seed: int) -> Dict[str, np.ndarray]:
    """Hourly CI per region: diurnal cycle (minimum at ``trough_hour``),
    AR(1) noise with coefficient 0.8, renewable ramps that cut CI by
    ``ramp_depth`` for ``ramp_hours``; floored at :data:`CI_FLOOR`."""
    out: Dict[str, np.ndarray] = {}
    t = np.arange(hours)
    for i, (name, p) in enumerate(sorted(regions.items())):
        ci = p.base - p.daily_amplitude * np.cos(
            2.0 * np.pi * (t - p.trough_hour) / 24.0)
        innov = np.random.default_rng([seed, 11, i]).normal(
            0.0, p.noise, size=hours)
        ar = np.zeros(hours)
        for k in range(1, hours):
            ar[k] = 0.8 * ar[k - 1] + innov[k]
        ci = ci + ar
        if p.ramp_prob > 0 and p.ramp_hours > 0:
            starts = np.random.default_rng([seed, 12, i]).random(hours) \
                < p.ramp_prob
            drop = np.zeros(hours)
            for k in np.nonzero(starts)[0]:
                drop[k:k + p.ramp_hours] = np.maximum(
                    drop[k:k + p.ramp_hours], p.ramp_depth)
            ci = ci * (1.0 - drop)
        out[name] = np.maximum(ci, CI_FLOOR)
    return out


class Carbon:
    """The grid carbon signal of one run (see the module docstring)."""

    def __init__(self, series: Mapping[str, np.ndarray], seed: int,
                 stream: int = 0):
        self.series = dict(series)
        self.seed, self.stream = seed, stream

    def history_signal(self, t: int):
        """Region -> the last week of CI up to and including hour ``t``,
        as a grid-intensity service returns it (a fixed length, so a tick
        late in a long window costs what an early one does)."""
        return lambda region: self.series[region][
            max(0, t + 1 - HISTORY_H): t + 1].tolist()

    def forecast(self, region: str, t: int, horizon: int) -> List[float]:
        """Level-corrected persistence: yesterday's cycle blended toward the
        current level with weight 0.7 ** lead (hour 0 = now)."""
        s = self.series[region]
        level = float(s[min(t, len(s) - 1)])
        out = []
        for h in range(horizon):
            src = t + h - 24
            cyc = float(s[max(src, 0)]) if src < t else level
            w = 0.7 ** h
            out.append(w * level + (1.0 - w) * cyc)
        return out

    def forecast_signal(self, t: int, horizon: int = 24):
        return lambda region: self.forecast(region, t, horizon)

    def scenario_matrix(self, node_regions: Sequence[str], t: int,
                        horizon: int = 24, B: int = 8) -> np.ndarray:
        """``[B, N]`` mean CI per node over the next ``horizon`` hours:
        branch 0 is the forecast, branches 1.. scale it by lognormal noise
        drawn from ``(seed, stream, 7919, t)``."""
        per_region = {r: float(np.mean(self.forecast(r, t, horizon)))
                      for r in set(node_regions)}
        base = np.array([per_region[r] for r in node_regions])
        rng = np.random.default_rng([self.seed, self.stream, 7919, t])
        out = np.empty((B, len(base)))
        out[0] = base
        for b in range(1, B):
            scale = rng.lognormal(0.0, SCENARIO_SIGMA, size=len(base))
            out[b] = np.maximum(base * scale, CI_FLOOR)
        return out

    def now(self, node_regions: Sequence[str], t: int) -> np.ndarray:
        """``[N]`` CI of every node at hour ``t``."""
        return np.array([self.series[r][t] for r in node_regions])


class Telemetry:
    """Every monitoring sample of a run: per (service, flavour) energy and
    per link traffic, ``samples`` per hourly window.  Utilisation of each
    sample is ``(1 + swing sin(2 pi (t - peak_hour) / 24)) (1 + drift t)
    (1 + N(0, noise))``, at least 0.05."""

    def __init__(self, dep: Microservices, hours: int, seed: int):
        p = dep.telemetry
        self.k_kwh_per_gb = float(p["k_kwh_per_gb"])
        k = int(p["samples"])
        self.cells: List[Tuple[str, str]] = [
            (s.sid, f.name) for s in dep.services for f in s.flavours]
        base = np.array([f.energy_kwh for s in dep.services
                         for f in s.flavours])
        first = {s.sid: s.flavours[0].name for s in dep.services}
        self.edges: List[Tuple[str, str, str]] = [
            (ln.src, first[ln.src], ln.dst) for ln in dep.links]
        self.size_gb = np.array([ln.size_gb for ln in dep.links])
        volume = np.array([ln.volume for ln in dep.links])
        rng = np.random.default_rng([seed, 21])
        t = np.arange(hours, dtype=float)[:, None, None]
        cyc = 1.0 + p["swing"] * np.sin(
            2.0 * np.pi * (t - p["peak_hour"]) / 24.0)
        trend = cyc * (1.0 + p["drift_per_h"] * t)

        def util(n):
            noise = rng.normal(0.0, p["noise"], size=(hours, n, k))
            return np.maximum(trend * (1.0 + noise), 0.05)

        self.energy = base[None, :, None] * util(len(base))   # [H, C, k]
        self.volume = volume[None, :, None] * util(len(volume))  # [H, L, k]

    def profiles(self, t: int, dtype=np.float64):
        """Eq. 1 and Eq. 2 of tick ``t``: mean energy per (service,
        flavour) and mean transmission energy per (source, source flavour,
        target), in ``dtype``."""
        e = self.energy[t].astype(dtype).mean(axis=1, dtype=dtype)
        c = (self.volume[t].astype(dtype) * self.size_gb[:, None].astype(dtype)
             * dtype(self.k_kwh_per_gb)).mean(axis=1, dtype=dtype)
        return (dict(zip(self.cells, e.tolist())),
                dict(zip(self.edges, c.tolist())))
