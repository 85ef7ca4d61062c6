"""The reduction from a profiler trace to device numbers, on a small
synthetic trace whose answers are worked out by hand."""
import pytest

from bench import devtrace


def test_merge_clips_and_joins_overlaps():
    assert devtrace.merge([(5, 8), (0, 2), (1, 3), (9, 20)], 1, 15) == [
        (1, 3), (5, 8), (9, 15)]


def test_gaps_are_the_complement_within_the_window():
    assert devtrace.gaps([(1, 3), (5, 8)], 0, 10) == [
        (0, 1), (3, 5), (8, 10)]
    assert devtrace.gaps([], 0, 4) == [(0, 4)]


def test_summary_of_a_two_chip_window():
    ns = 1e9
    annotations = [
        ("bench.window", 0.0, 10 * ns),
        ("bench.tick", 0.0, 4 * ns),
        ("bench.tick", 5 * ns, 10 * ns),
    ]
    # a loop from 1 to 3 s holding a fusion (1..1.5) and a dot (2..2.5)
    chip0 = [("while", 1 * ns, 3 * ns), ("fusion", 1 * ns, 1.5 * ns),
             ("dot", 2 * ns, 2.5 * ns), ("fusion", 6 * ns, 7 * ns)]
    chip1 = [("fusion", 0.0, 1 * ns), ("copy", 9 * ns, 12 * ns)]
    s = devtrace.summarize([chip0, chip1], annotations)
    # chip 0 busy 1..3 and 6..7 (3 s), chip 1 busy 0..1 and 9..10 (2 s)
    assert s["busy_s"] == pytest.approx(2.5)
    assert s["window_s"] == pytest.approx(10.0)
    ops = dict(s["device_ops"])
    assert ops["fusion"] == pytest.approx(1.25)   # (0.5 + 1 + 1) s / 2
    assert ops["while"] == pytest.approx(0.5)     # 2 s less 1 s inside
    assert ops["dot"] == pytest.approx(0.25)
    assert ops["copy"] == pytest.approx(0.5)      # clipped at the window
    # chip 0's gaps: 3..6 (3 s, mid 4.5: between ticks), 7..10, 0..1
    assert s["idle_gaps"] == [["between items", 3.0], ["tick", 3.0],
                              ["tick", 1.0]]


def test_self_times_of_nested_ops():
    ops = [("while", 0, 10), ("body", 1, 9), ("a", 2, 3), ("a", 4, 6),
           ("b", 12, 13)]
    assert devtrace.self_times(ops) == {"while": 2, "body": 5, "a": 3,
                                        "b": 1}


def test_op_names_are_the_instruction_names():
    assert devtrace.op_name("%fusion.40 = f32[4]{0} fusion(f32[4] %p)") \
        == "fusion.40"


def test_no_window_or_no_device_reads_nothing():
    assert devtrace.summarize([[("x", 0, 1)]], []) is None
    assert devtrace.summarize([], [("bench.window", 0, 1)]) is None
    assert devtrace.summarize([[]], [("bench.window", 0, 1)]) is None
