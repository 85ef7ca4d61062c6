"""Faults planted under the timed path, for the tests that see ``correct``
come out false: each wraps one function of the program for the length
of a ``with plant(name):`` block."""
from __future__ import annotations

import contextlib
import importlib
import json

import numpy as np


def _scaled(orig):
    """An answer altered where it is produced: emissions off by 1e-6."""
    def f(*args, **kwargs):
        return orig(*args, **kwargs) * (1.0 + 1e-6)
    return f


def _never_switch(orig):
    """The tick hands its state back unchanged: no switch after the
    initial rollout."""
    def gate(self, cand, saving_g, want_cells=False, force=False):
        if self.current is None:
            return orig(self, cand, saving_g, want_cells, force)
        return False, 0, 0, 0.0, ()
    return gate


def _half_branches(orig):
    """Half of the forecast ensemble left out, the mean taken over the
    rest."""
    def f(low, assignments, scenarios):
        em = orig(low, assignments, scenarios)
        B = em.shape[1]
        h = max(1, B // 2)
        return np.concatenate([em[:, :h]] * (B // h + 1), axis=1)[:, :B]
    return f


def _initial_state(orig):
    """The fused scan hands back its initial state unchanged."""
    def commit(runtime, st, carry_out, ys, *args, **kwargs):
        return orig(runtime, st, st.carry0, ys, *args, **kwargs)
    return commit


def _half_services(orig):
    """Half of the services left out of the committed state."""
    def commit(runtime, st, carry_out, ys, *args, **kwargs):
        placed = np.array(carry_out[0], copy=True)
        placed[len(placed) // 2:] = False
        return orig(runtime, st, (placed,) + tuple(carry_out[1:]), ys,
                    *args, **kwargs)
    return commit


def _no_search(orig):
    """The planner's local search left out: each branch keeps its greedy
    placement, or the incumbent it starts from."""
    def post_init(self):
        orig(self)
        self.local_search_rounds = 0
    return post_init


def _saving_scaled(orig):
    """The fused scan's expected saving altered where it is produced."""
    def commit(runtime, st, carry_out, ys, *args, **kwargs):
        ys = tuple(ys)
        ys = ys[:6] + (ys[6] * (1.0 + 1e-6),) + ys[7:]
        return orig(runtime, st, carry_out, ys, *args, **kwargs)
    return commit


# fault name -> (module, attribute path, wrapper)
FAULTS = {
    "tick.answer_altered": ("repro.continuum.loop", "lowered_emissions",
                            _scaled),
    "tick.state_unchanged": ("repro.continuum.loop",
                             "ContinuumRuntime.hysteresis_gate",
                             _never_switch),
    "tick.half_batch": ("repro.continuum.loop", "ensemble_emissions",
                        _half_branches),
    "tick.search_skipped": ("repro.core.scheduler",
                            "SchedulerConfig.__post_init__", _no_search),
    "replay.answer_altered": ("repro.continuum.megaloop",
                              "lowered_emissions", _scaled),
    "replay.state_unchanged": ("repro.continuum.megaloop", "_commit",
                               _initial_state),
    "replay.half_batch": ("repro.continuum.megaloop", "_commit",
                          _half_services),
    "replay.saving_altered": ("repro.continuum.megaloop", "_commit",
                              _saving_scaled),
    "replay.search_skipped": ("repro.core.scheduler",
                              "SchedulerConfig.__post_init__", _no_search),
}


@contextlib.contextmanager
def plant(name: str):
    module, path, wrap = FAULTS[name]
    obj = importlib.import_module(module)
    *owners, attr = path.split(".")
    for o in owners:
        obj = getattr(obj, o)
    orig = getattr(obj, attr)
    setattr(obj, attr, wrap(orig))
    try:
        yield
    finally:
        setattr(obj, attr, orig)


RUN = """
import json, sys
from bench import harness
from bench.tests import faults
spec = harness.load_spec()
for w in spec["workloads"]:
    w["chips"] = 1
case = json.loads(sys.argv[1])
out = {}
for name in case["faults"]:
    with (faults.plant(name) if name != "none"
          else faults.contextlib.nullcontext()):
        cell = harness.Cell(case["workload"], case["seed"], False,
                            require_tpu=False, overrides=case["overrides"],
                            spec=spec)
        cell.window(1.0)
        numbers = cell.driver.judge(cell.driver.answers())
    out[name] = {k: [v, lim] for k, (v, lim) in numbers.items()}
print(json.dumps(out))
"""


def readings(root, workload: str, names, seed: int, overrides) -> dict:
    """The numbers compared, ``{fault: {number: [value, limit]}}``, of a
    run of ``workload`` with each fault planted in turn ("none": sound)."""
    from .cells import run_python

    case = {"workload": workload, "faults": list(names), "seed": seed,
            "overrides": overrides}
    proc = run_python(root, ["-c", RUN, json.dumps(case)])
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
