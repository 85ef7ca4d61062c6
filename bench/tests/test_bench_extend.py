"""A configuration, a traffic mix, a cell and a per-layer metric added by
new files and new entries alone, with no file of the harness edited."""
import json

from .cells import bench_copy, run_harness

NEW = "boutique2.short-tick"


def test_new_entries_and_files_make_a_cell(tmp_path):
    root = bench_copy(tmp_path)
    b = root / "bench"
    cfg = json.loads((b / "configs/online-boutique-eu.json").read_text())
    keep = ("france", "spain")
    cfg.update(name="boutique-two-nodes",
               nodes={k: cfg["nodes"][k] for k in keep},
               regions={k: cfg["regions"][k] for k in keep})
    (b / "configs/boutique-two-nodes.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic/eager-tick.json").read_text())
    mix.update(max_ticks=40, trace_items=3)
    (b / "traffic/short-tick.json").write_text(json.dumps(mix))
    (b / "metrics/ticks_seen.tick.py").write_text(
        "def read(inputs):\n    return float(inputs['ticks'])\n")

    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "boutique-two-nodes", "source": "a test entry",
        "file": "bench/configs/boutique-two-nodes.json", "reduced": [],
        "why": "a test entry"})
    spec["workloads"].append({
        "name": NEW, "config": "boutique-two-nodes",
        "traffic": "short-tick", "chips": 1, "why": "a test entry"})
    for m in spec["end_to_end"]:
        if m["name"] == "tick_ms_p50":
            m["workloads"].append(NEW)
    spec["per_layer"].append({
        "name": "ticks_seen.tick", "unit": "ticks", "better": "higher",
        "source": "program_counter", "layer": "planner",
        "moves": "tick_ms_p50", "workloads": [NEW]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    _, line = run_harness(root, NEW, overrides={})
    assert line["correct"] is True
    assert set(line["metrics"]) == {"tick_ms_p50", "setup_s"}
    _, line = run_harness(root, NEW, trace=True, overrides={})
    assert line["correct"] is True
    assert line["metrics"]["ticks_seen.tick"]["value"] > 0
