"""A cell of the real configuration and traffic, shrunk to what a test
run on the CPU can hold, and a run of it that skips the look for a chip."""
import atexit
import copy
import functools
import json
import shutil
import tempfile
import time
from pathlib import Path

from bench import freeze_world, harness

BENCH = Path(harness.BENCH_DIR)
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@functools.lru_cache(maxsize=None)
def _frozen(config_json: str) -> str:
    where = tempfile.mkdtemp(prefix="bench-world-")
    atexit.register(shutil.rmtree, where, True)
    return freeze_world.freeze(json.loads(config_json),
                               str(Path(where) / "world.npz"))


def tiny_config(name="jiagu-1k", **sizes):
    """The configuration at a test's size (``sizes`` overrides its
    top-level keys), with its own frozen world."""
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update({"target_nodes": 24, "n_functions": 8, **sizes})
    cfg["prediction"].update(n_train=300, n_trees=8, max_depth=6)
    cfg["world_file"] = _frozen(json.dumps(cfg, sort_keys=True))
    return cfg


def tiny_cell(traffic="storm", name="jiagu-1k"):
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    units = {"setup_s": "s", "tick_ms": "ms/fleet_s", "place_ms_p50": "ms",
             "place_ms_p90": "ms", "refresh_ms": "ms"}
    return harness.Cell(f"tiny.{traffic}", 1, tiny_config(name), t,
                        list(units), [], {}, units)


def run(cell, seed=5, seconds=1.0, traced=False, trace_dir=None):
    return harness.run_cell(copy.deepcopy(cell), seed, seconds, traced,
                            time.perf_counter(), FAKE_DEVICE, trace_dir)
