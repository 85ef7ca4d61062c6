"""``boutique.replay``: with the timed path broken underneath, ``correct``
comes out false; sound, it comes out true."""
import pytest

from . import faults
from .cells import SEED, TINY, bench_copy

WORKLOAD = "boutique.replay"
NAMES = sorted(n for n in faults.FAULTS if n.startswith("replay."))


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = bench_copy(tmp_path_factory.mktemp("bench"))
    return faults.readings(root, WORKLOAD, ["none"] + NAMES, SEED,
                           TINY[WORKLOAD])


def test_sound_run_is_correct(readings):
    assert all(v <= lim for v, lim in readings["none"].values()), \
        readings["none"]


@pytest.mark.parametrize("fault", NAMES)
def test_fault_is_not_correct(readings, fault):
    assert any(v > lim for v, lim in readings[fault].values()), \
        readings[fault]
