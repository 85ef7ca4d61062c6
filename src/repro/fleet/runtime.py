"""FleetRuntime: the adaptive continuum loop at multi-tenant scale.

One :class:`~repro.continuum.loop.ContinuumRuntime` drives one
application.  The fleet runtime drives A of them over the SAME
infrastructure and carbon trace: each tick it runs every app's
constraint pipeline (profiles, KB, constraints — per-app state), bundles
the resulting problems into a :class:`FleetProblem`, replans the whole
fleet in one ``plan_many`` call (waterfill coupling by default, so
tenants can't jointly over-commit a node), and then applies the
EXISTING per-app hysteresis gate — switch only when the expected saving
beats migration+restart cost plus the hysteresis margin — before
accounting each app's ACTIVE assignment under the tick's true carbon
intensities.

The commit keeps capacity whatever the coupling: the gates' verdicts
are committed together only where the holders' incumbents and the
switchers' candidates fit every node together.  Otherwise each switch,
in priority order, replaces its incumbent's load by its candidate's only
where every node stays within capacity, and holds where not; a first
rollout that does not fit is planned by the waterfill program into the
capacity left, and refused where even that fails.  (An emergency tick
adopts the coupled plan atomically instead.)

Spans, with a tracer attached (``FleetRuntime(tracer=...)`` or an
``Observability`` bundle's), one tree per tick:

    fleet.tick
    ├── fleet.ingest       per-tenant telemetry, constraints, lowering
    │   │                  (enrichments, enrich_shared)
    │   ├── fleet.telemetry    the carbon signals, the shared machines'
    │   │                      enrichment and the monitoring window
    │   ├── fleet.constraints  the constraint pass
    │   └── fleet.lower        lowering, fault masking, warm start,
    │                          the fleet problem
    ├── fleet.plan         (apps, padded_apps, calls, devices,
    │   │                   price_rounds, overcommitted)
    │   ├── fleet.prepare
    │   ├── fleet.round    one per price round (or pass)
    │   │   ├── fleet.fold       price fold and stacking
    │   │   ├── fleet.dispatch   per call (h2d_bytes)
    │   │   ├── fleet.wait       per call
    │   │   ├── fleet.fetch      per call (d2h_bytes)
    │   │   └── fleet.loads      load and price update (price only)
    │   └── fleet.finalize
    └── fleet.commit       gates, the capacity rule, accounting
                           (switched, held, repaired, refused)

The children of ``fleet.tick``, ``fleet.plan`` and each ``fleet.round``
tile their parent in order.  The children of ``fleet.ingest`` are each
stage's time summed over the tenants, laid end to end from the ingest's
start (the tenants' stages interleave).

Multi-tenant billing rides on the shared observability ledger: every
app's tick entry is recorded with its tenant tag (``app=name``), so
``repro.obs.billing_report`` decomposes the fleet's total gCO2 into
per-tenant comp/comm/migration bills whose addends are bit-equal to the
per-tick accounted emissions.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.continuum.loop import (
    ContinuumResult,
    ContinuumRuntime,
    GateDecision,
    RuntimeConfig,
    TickRecord,
    account,
    kept_hooks,
)
from repro.continuum.traces import CarbonTrace, WorkloadTrace
from repro.continuum.whatif import assignment_arrays, plan_assignment
from repro.core.lowering import lowered_emissions
from repro.faults import PlacementViolation, check_placement
from repro.core.problem import BucketSpec
from repro.core.scheduler import (
    COMPILE_CACHE,
    GreenScheduler,
    SchedulerConfig,
)
from repro.core.types import Application, Infrastructure
from repro.obs import Observability, Tracer

from .planner import plan_many
from .problem import (
    _CAP_EPS,
    CapacityReport,
    FleetProblem,
    FleetStage,
    FleetStats,
    accumulate_loads,
    empty_capacity_report,
)

__all__ = ["FleetApp", "FleetRuntime", "FleetRunResult", "FleetTickRecord"]


@dataclass
class FleetApp:
    """One tenant: an application with its own workload trace and
    waterfilling priority (higher plans first)."""

    name: str
    app: Application
    workload: WorkloadTrace
    priority: float = 0.0


@dataclass
class FleetTickRecord:
    """One fleet tick: every tenant's :class:`TickRecord` plus the
    shared-capacity accounting of the ACTIVE (post-hysteresis)
    assignments and of the tick's candidate plans."""

    t: int
    records: Dict[str, TickRecord]
    capacity: CapacityReport          # active assignments
    planned_capacity: CapacityReport  # this tick's plan_many candidates
    plan_stats: FleetStats
    compiles: int = 0                 # XLA programs built this tick
    # the capacity rule's outcome: switches that did not fit and held,
    # first rollouts planned into the capacity left, and tenants left
    # with nothing deployed
    held: Tuple[str, ...] = ()
    repaired: Tuple[str, ...] = ()
    refused: Tuple[str, ...] = ()

    @property
    def emissions_g(self) -> float:
        return sum(r.emissions_g for r in self.records.values())

    @property
    def migration_g(self) -> float:
        return sum(r.migration_g for r in self.records.values())

    @property
    def violations(self) -> int:
        return self.capacity.violations


@dataclass
class FleetRunResult:
    """``FleetRuntime.run`` output: fleet-level tick records plus one
    per-tenant :class:`ContinuumResult` (same schema as a single-app
    run, so every existing reporting/serialization path applies
    per tenant)."""

    ticks: List[FleetTickRecord]
    results: Dict[str, ContinuumResult]

    @property
    def total_emissions_g(self) -> float:
        return sum(r.total_emissions_g for r in self.results.values())

    def summary(self) -> Dict[str, float]:
        return {
            "ticks": len(self.ticks),
            "apps": len(self.results),
            "total_emissions_g": self.total_emissions_g,
            "migration_emissions_g": sum(
                fr.migration_g for fr in self.ticks),
            "violations": sum(fr.violations for fr in self.ticks),
            "switches": sum(
                r.switched for fr in self.ticks
                for r in fr.records.values()),
        }


def _default_scheduler(config: RuntimeConfig) -> GreenScheduler:
    bucket = config.bucket if config.bucket is not None else BucketSpec()
    return GreenScheduler(SchedulerConfig(
        emission_weight=1.0, bucket=bucket))


@dataclass
class FleetRuntime:
    """Drive A tenants' adaptive loops with one fleet replan per tick."""

    apps: List[FleetApp]
    infra: Infrastructure
    carbon: CarbonTrace
    config: RuntimeConfig = field(default_factory=RuntimeConfig)
    coupling: str = "waterfill"
    scheduler: Optional[GreenScheduler] = None
    obs: Optional[Observability] = field(default=None, repr=False)
    # Green watchtower: per-tenant SLOs (slo.tenant == the FleetApp
    # name) are priced off each tenant's accounted per-tick totals —
    # the same values the shared ledger bills, so SLO budget spend is
    # bit-equal to billing_report's per-tenant sums.
    watch: Optional[object] = field(default=None, repr=False)
    max_batch: int = 256
    # the jax devices plan_many runs on (default every visible device)
    devices: Optional[Sequence] = field(default=None, repr=False)
    # A tracer on its own; an attached bundle's tracer takes its place.
    tracer: Optional[Tracer] = field(default=None, repr=False)
    # the last tick's plan_many result (its candidates, before the gates)
    last_result: Optional[object] = field(default=None, init=False,
                                          repr=False)

    def __post_init__(self) -> None:
        names = [fa.name for fa in self.apps]
        if len(set(names)) != len(names):
            raise ValueError(f"fleet app names must be unique: {names!r}")
        if self.scheduler is None:
            self.scheduler = _default_scheduler(self.config)
        self._node_regions = [
            n.region or n.node_id for n in self.infra.nodes]
        # One ContinuumRuntime per tenant as the per-app state holder:
        # its pipeline owns the profiles/KB/lowering caches, its
        # ``current`` the incumbent assignment, and its hysteresis_gate
        # the switch rule — the fleet runtime only replaces the REPLAN
        # step with the batched plan_many call.  With a fault schedule
        # each per-app runtime also carries the degraded carbon/workload
        # views, which the fleet tick reads through.
        self._runtimes: Dict[str, ContinuumRuntime] = {
            fa.name: ContinuumRuntime(
                app=fa.app, infra=self.infra, carbon=self.carbon,
                workload=fa.workload, config=self.config)
            for fa in self.apps}
        # post-plan invariant violations across all tenants (the
        # capacity check runs on the SUMMED multi-tenant loads)
        self.placement_violations: List[PlacementViolation] = []

    def runtime(self, name: str) -> ContinuumRuntime:
        return self._runtimes[name]

    def active_tracer(self) -> Optional[Tracer]:
        """The tracer the fleet tick records spans into: an enabled
        bundle's, else the lone ``tracer``; None when neither records."""
        tr = self.obs.tracer if (self.obs is not None and self.obs.enabled) \
            else self.tracer
        return tr if tr is not None and tr.enabled else None

    def tick(self, t: int) -> FleetTickRecord:
        cfg = self.config
        obs = self.obs if (self.obs is not None and self.obs.enabled) \
            else None
        misses0 = COMPILE_CACHE.misses
        t_tick0 = time.perf_counter()

        # 1+2. each tenant runtime's ingest, constraint pass and fault
        # stage -> one problem per app, warm-started from its incumbent.
        # With a fault schedule the ingestion goes through each runtime's
        # degraded views, dead/derated nodes are masked out of every
        # tenant's lowering, and stranded services are evicted
        # (re-placement is an emergency that bypasses the per-app
        # hysteresis gate).
        faults = cfg.faults
        alive = faults.alive_at(t) if faults is not None else None
        derate = faults.derate_at(t) if faults is not None else None
        problems = []
        outs = []
        evicted: Dict[str, int] = {}
        emergency: Dict[str, bool] = {}
        # seconds summed over the tenants: telemetry, constraints, lowering
        # (with the fleet problem's build)
        ingest_s = [0.0, 0.0, 0.0]
        # The gatherer's enrichment of the shared machines, once for each
        # group of tenants that would compute the same one: the same
        # carbon view (without a fault schedule, the fleet's own trace)
        # and gatherer settings; the tick and horizon are the fleet's.
        enriched: Dict[Tuple, Tuple[object, Infrastructure]] = {}
        t0 = t_tick0
        for fa in self.apps:
            rt = self._runtimes[fa.name]
            mon = rt.ingest(t)
            view, gatherer = rt.carbon_view, rt.pipeline.gatherer
            key = (id(view), type(gatherer), gatherer.window,
                   gatherer.forecast_from_history)
            if key not in enriched:
                # the view is held so that its id stays its own all tick
                enriched[key] = (view, gatherer.enrich(self.infra))
            t1 = time.perf_counter()
            out = rt.constraint_pass(mon, t, enriched=enriched[key][1])
            t2 = time.perf_counter()
            problem, evicted[fa.name], emergency[fa.name] = rt.fault_stage(
                rt.pipeline.problem_for(out), t, alive, derate)
            if cfg.warm_start and rt.current is not None:
                problem = problem.with_warm_start(rt.current)
            problems.append(problem)
            outs.append(out)
            t3 = time.perf_counter()
            ingest_s[0] += t1 - t0
            ingest_s[1] += t2 - t1
            ingest_s[2] += t3 - t2
            t0 = t3

        fleet = FleetProblem(
            apps=tuple(problems),
            names=tuple(fa.name for fa in self.apps),
            priority=tuple(fa.priority for fa in self.apps),
            coupling=self.coupling)
        ingest_s[2] += time.perf_counter() - t0

        # 3. one batched fleet replan (coupled capacity per ``coupling``)
        t_plan0 = time.perf_counter()
        fresult = plan_many(fleet, self.scheduler,
                            max_batch=self.max_batch, devices=self.devices)
        t_plan1 = time.perf_counter()
        replan_s = t_plan1 - t_plan0
        self.last_result = fresult
        ci_now = self.carbon.now(self._node_regions, t)

        # 4. per-tenant hysteresis gate, decided before anything switches.
        # An emergency anywhere forces the WHOLE fleet's coupled plan:
        # plan_many's candidates are only jointly capacity-feasible as a
        # set, so letting one tenant's flap damping hold its incumbent
        # while another evacuates onto the coupled plan could overcommit
        # a node.  Atomic adoption keeps the invariant; every forced
        # move is still billed in full.
        fleet_force = any(emergency.values())
        if fleet_force:
            for fa in self.apps:
                emergency[fa.name] = True
        cands: List[Optional[Dict[str, Tuple[str, str]]]] = []
        decisions: List[Optional[GateDecision]] = []
        savings: List[float] = []
        for i, fa in enumerate(self.apps):
            rt = self._runtimes[fa.name]
            plan = fresult.results[i].plans[0]
            cand, decision, saving = None, None, 0.0
            if plan.feasible:
                cand = plan_assignment(plan)
                if rt.current is not None and cand != rt.current:
                    # expected saving under the tick's MONITORED signal
                    # (low.ci): candidate emissions are exactly the
                    # planner's per-app value, the incumbent re-priced
                    # on the same lowering
                    low = problems[i].lowering
                    cur_g = lowered_emissions(
                        low, *assignment_arrays(low, rt.current))
                    saving = (cur_g - float(fresult.emissions_g[i])) \
                        * cfg.horizon_h
                decision = rt.gate_decision(cand, saving,
                                            force=emergency[fa.name])
            cands.append(cand)
            decisions.append(decision)
            savings.append(saving)

        # 5. the switches that keep capacity, then first rollouts that
        # did not fit planned into the capacity left
        adopt, held, deferred, used = self._fit(
            fleet, problems, cands, decisions, fleet_force)
        repairs = self._repair(fleet, deferred, used) if deferred else {}

        # 6. switch and account each tenant's ACTIVE assignment under the
        # tick's true CI
        records: Dict[str, TickRecord] = {}
        cpu_load = np.zeros(len(self._node_regions))
        ram_load = np.zeros(len(self._node_regions))
        viols_before = len(self.placement_violations)
        refused: List[str] = []
        for i, fa in enumerate(self.apps):
            rt = self._runtimes[fa.name]
            low = problems[i].lowering
            plan = fresult.results[i].plans[0]
            warm_rejected = any(
                "warm start rejected" in n for n in plan.notes)
            switched = False
            migrations = restarts = 0
            charged_moved = charged_flapped = 0
            migration_g = 0.0
            mig_cells: Tuple = ()
            if i in repairs:
                switched, migrations = True, len(repairs[i])
                rt.adopt(repairs[i])
            elif i in adopt:
                d = decisions[i]
                initial = rt.current is None
                mig_cells = rt.adopt(cands[i], want_cells=obs is not None)
                switched, migrations, restarts, migration_g = \
                    True, d.migrations, d.restarts, d.migration_g
                if not initial:
                    charged_moved = migrations
                    charged_flapped = restarts
            if not rt.current and low.S:
                refused.append(fa.name)
            emissions = 0.0
            placed = fcur = ncur = None
            viols: List[PlacementViolation] = []
            if rt.current:
                placed, fcur, ncur = assignment_arrays(low, rt.current)
                accumulate_loads(low, placed, fcur, ncur,
                                 cpu_load, ram_load)
                # liveness per tenant here; capacity runs once on the
                # SUMMED loads after every tenant is accounted
                emissions, viols = account(
                    low, placed, fcur, ncur, ci_now, alive=alive, t=t,
                    validate=cfg.validate_placements,
                    cpu_load=np.zeros(low.N), ram_load=np.zeros(low.N))
                self.placement_violations.extend(viols)
            records[fa.name] = TickRecord(
                t=t, emissions_g=emissions, migration_g=migration_g,
                migrations=migrations, replanned=True, switched=switched,
                expected_saving_g=savings[i],
                n_constraints=len(outs[i].constraints),
                warm_start_rejected=warm_rejected, restarts=restarts,
                replan_s=replan_s, evicted=evicted[fa.name],
                emergency=emergency[fa.name], violations=len(viols))
            if obs is not None:
                obs.ledger.record(
                    t, low, placed, fcur, ncur, ci_now,
                    zones=self._node_regions,
                    moved=charged_moved, flapped=charged_flapped,
                    migration_fee_g=cfg.migration_g,
                    restart_fee_g=cfg.restart_g,
                    mig_cells=mig_cells, app=fa.name)

        if problems:
            ref = problems[0].lowering
            if cfg.validate_placements:
                # shared-capacity invariant on the SUMMED tenant loads,
                # against the (possibly derated) capacity tensors
                zs = np.zeros(ref.S, np.int64)
                self.placement_violations.extend(check_placement(
                    ref, np.zeros(ref.S, bool), zs, zs, t=t,
                    cpu_load=cpu_load, ram_load=ram_load))
            capacity = CapacityReport(
                node_ids=tuple(n.node_id for n in self.infra.nodes),
                cpu_load=cpu_load, ram_load=ram_load,
                cpu_cap=np.asarray(ref.cpu_cap, dtype=float),
                ram_cap=np.asarray(ref.ram_cap, dtype=float))
        else:
            capacity = empty_capacity_report()
        if obs is not None and faults is not None and self.apps:
            # one fault-event record per tick for the whole fleet
            self._runtimes[self.apps[0].name]._record_fault_events(
                obs, t, sum(evicted.values()), any(emergency.values()),
                self.placement_violations[viols_before:])
        if self.watch is not None and self.apps:
            self.watch.observe_fleet_tick(
                t, records, ci_now,
                registry=obs.registry if obs is not None else None)
        names = fleet.names
        frec = FleetTickRecord(
            t=t, records=records, capacity=capacity,
            planned_capacity=fresult.capacity,
            plan_stats=fresult.stats,
            compiles=COMPILE_CACHE.misses - misses0,
            held=tuple(names[i] for i in held),
            repaired=tuple(names[i] for i in sorted(repairs)),
            refused=tuple(refused))
        tr = self.active_tracer()
        if tr is not None:
            t_commit1 = time.perf_counter()
            st = fresult.stats
            # the plan is what its stages time: the call's own entry and
            # return fall to the ingest and the commit
            t_plan0, t_plan1 = st.stages[0].t0, st.stages[-1].t1
            tid = tr.add("fleet.tick", t_tick0, t_commit1, t=t)
            iid = tr.add("fleet.ingest", t_tick0, t_plan0, parent=tid,
                         enrichments=len(enriched),
                         enrich_shared=len(self.apps) - len(enriched))
            t0 = t_tick0
            for name, dt in zip(_INGEST_SPANS, ingest_s):
                tr.add(name, t0, t0 + dt, parent=iid)
                t0 += dt
            pid = tr.add("fleet.plan", t_plan0, t_plan1, parent=tid,
                         apps=st.apps, padded_apps=st.padded_apps,
                         calls=st.calls, devices=st.devices,
                         price_rounds=st.price_rounds,
                         overcommitted=fresult.capacity.violations)
            _stage_spans(tr, pid, st.stages)
            tr.add("fleet.commit", t_plan1, t_commit1, parent=tid,
                   switched=sum(r.switched for r in records.values()),
                   held=len(frec.held), repaired=len(frec.repaired),
                   refused=len(frec.refused))
        return frec

    def _fit(self, fleet: FleetProblem, problems, cands, decisions,
             force: bool) -> Tuple[Set[int], List[int], List[int], Tuple]:
        """The switches the capacity allows: ``(adopt, held, deferred,
        used)``.  ``deferred`` are first rollouts that did not fit,
        ``used`` the per-node ``(cpu, ram)`` loads once ``adopt`` has
        switched and the others hold."""
        switching = [i for i, d in enumerate(decisions)
                     if d is not None and d.switch]
        if force or not problems:
            return set(switching), [], [], ()
        N = len(self._node_regions)
        cap = (np.asarray(problems[0].lowering.cpu_cap, dtype=float),
               np.asarray(problems[0].lowering.ram_cap, dtype=float))

        def load(i, assign):
            cpu, ram = np.zeros(N), np.zeros(N)
            if assign:
                low = problems[i].lowering
                accumulate_loads(low, *assignment_arrays(low, assign),
                                 cpu, ram)
            return cpu, ram

        inc = [load(i, self._runtimes[fa.name].current)
               for i, fa in enumerate(self.apps)]
        new = {i: load(i, cands[i]) for i in switching}
        together = [sum(new[i][k] if i in new else inc[i][k]
                        for i in range(len(inc))) for k in (0, 1)]
        if all((together[k] <= cap[k] + _CAP_EPS).all() for k in (0, 1)):
            return set(switching), [], [], tuple(together)
        # from every incumbent (they fit, by the last tick's commit), each
        # switch in priority order where every node it loads stays within
        # capacity; a node already past it may only get lighter
        used = [sum(inc[i][k] for i in range(len(inc))) for k in (0, 1)]
        adopt: Set[int] = set()
        held: List[int] = []
        deferred: List[int] = []
        for i in fleet.waterfill_order():
            if i not in new:
                continue
            trial = [used[k] - inc[i][k] + new[i][k] for k in (0, 1)]
            if all(((trial[k] <= cap[k] + _CAP_EPS)
                    | (trial[k] <= used[k])).all() for k in (0, 1)):
                used = trial
                adopt.add(i)
            elif self._runtimes[fleet.names[i]].current is None:
                deferred.append(i)
            else:
                held.append(i)
        return adopt, held, deferred, tuple(used)

    def _repair(self, fleet: FleetProblem, deferred: List[int],
                used: Tuple) -> Dict[int, Dict[str, Tuple[str, str]]]:
        """First rollouts that did not fit, planned by the waterfill
        program into the capacity ``used`` leaves, in priority order;
        each chunk padded to ``max_batch`` apps, so every repair runs one
        compiled program.  Returns the feasible plans by tenant index."""
        sched = self.scheduler
        bucket = sched.config.bucket if sched.config.bucket is not None \
            else BucketSpec()
        sub = FleetProblem(
            apps=tuple(fleet.apps[i] for i in deferred),
            names=tuple(fleet.names[i] for i in deferred),
            priority=tuple(fleet.priority[i] for i in deferred),
            coupling="waterfill")
        devices = None if self.devices is None else self.devices[:1]
        res = plan_many(sub, sched, max_batch=self.max_batch,
                        bucket=dataclasses.replace(bucket,
                                                   a=(self.max_batch,)),
                        devices=devices, used=used)
        return {i: plan_assignment(r.plans[0])
                for i, r in zip(deferred, res.results)
                if r.plans[0].feasible}

    def run(self, start: int, ticks: int) -> FleetRunResult:
        with kept_hooks(*(rt.pipeline.gatherer
                          for rt in self._runtimes.values())):
            frecs = [self.tick(t) for t in range(start, start + ticks)]
        results = {
            fa.name: ContinuumResult(
                ticks=[fr.records[fa.name] for fr in frecs],
                final_assignment=dict(
                    self._runtimes[fa.name].current or {}))
            for fa in self.apps}
        return FleetRunResult(ticks=frecs, results=results)


_INGEST_SPANS = ("fleet.telemetry", "fleet.constraints", "fleet.lower")


def _stage_spans(tr: Tracer, parent: int, stages: List[FleetStage]) -> None:
    """A ``plan_many`` call's timed stages as spans under ``parent``."""
    for st in stages:
        sid = tr.add(st.name, st.t0, st.t1, parent=parent, **st.attrs)
        _stage_spans(tr, sid, st.children)
