"""Drivers: one per entry point of the program that a traffic mix drives.

A traffic file names its driver (``"driver": "eager_tick"``); the harness
loads ``bench/drivers/<driver>.py`` and uses its ``Driver`` class:

* ``Driver(deployment, mix, seed, devices, traced)`` builds the program's
  inputs and objects from the seed and warms up every shape the window
  uses (all of it set-up);
* ``step()`` runs one timed item (a tick, a chunk, a replan) and keeps
  its answers; ``exhausted`` says when the generated trace has run out;
* ``end_to_end(window_s)`` gives the end-to-end metrics, ``counts()``
  the items attempted and failed, ``layer_inputs()`` what the per-layer
  readers read;
* ``answers()`` the program's answers, ``control_answers()`` the same
  recomputed by the reference in float32, and ``judge(answers)`` the
  numbers compared, each as ``(value, limit)``.
"""
from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"bench.drivers.{name}").Driver
