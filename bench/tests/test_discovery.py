"""A cell is found from files alone: a configuration, a traffic mix and
a per-layer metric added in a new directory become a runnable cell
without an edit to any file of the benchmark."""
import json
import os
import subprocess
import sys
import time

from bench import harness
from bench.tests import helpers

READER = '''
def read(run):
    return float(run.fleet_s) if run.fleet_s else None
'''


def _tree(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(helpers.tiny_config()))
    traffic = json.loads((helpers.BENCH / "traffic" / "storm.json")
                         .read_text())
    traffic["params"]["period_s"] = 20
    (bench / "traffic" / "gusts.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "fleet_seconds.py").write_text(READER)
    spec = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.gusts", "config": "tiny",
                       "traffic": "gusts", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "tick_ms", "unit": "ms/fleet_s",
                        "workloads": ["tiny.gusts"]},
                       {"name": "refresh_ms", "unit": "ms",
                        "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "fleet_seconds", "unit": "fleet_s",
                       "workloads": ["tiny.gusts"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_new_files_make_a_runnable_cell(tmp_path):
    root = _tree(tmp_path)
    cell = harness.find_cell("tiny.gusts", root=root)
    assert cell.end_to_end == ["setup_s", "tick_ms"]
    assert cell.per_layer == ["fleet_seconds"]
    assert cell.traffic["params"]["period_s"] == 20
    plain = harness.run_cell(cell, 3, 1.0, False, time.perf_counter(),
                             helpers.FAKE_DEVICE)
    assert set(plain["metrics"]) == {"setup_s", "tick_ms"}
    traced = harness.run_cell(cell, 3, 1.0, True, time.perf_counter(),
                              helpers.FAKE_DEVICE, root / "trace")
    assert traced["metrics"]["fleet_seconds"]["value"] > 0
    assert traced["metrics"]["fleet_seconds"]["unit"] == "fleet_s"


def test_no_tpu_no_result(tmp_path):
    """Without a TPU the command exits non-zero and prints no result."""
    root = os.path.dirname(harness.BENCH_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                        "--workload", "jiagu-1k.storm", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
