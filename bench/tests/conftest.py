"""The benchmark's own tests (run explicitly: ``pytest bench/tests``).
They run on the CPU, at small sizes."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import json  # noqa: E402

import pytest  # noqa: E402

TINY = {"generator": "grid2d", "params": {"nx": 24, "ny": 24, "seed": 11},
        "n": 576, "m": 1104, "fill_slack": 64}


def tiny_cell(traffic: str, config: str = "grid2d_256", **override):
    """A cell of the real configuration and traffic mix, cut to a graph
    the CPU factors in seconds."""
    from bench import harness, loadgen
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = harness.load_config(ROOT / "bench" / "configs" / f"{config}.json")
    cfg.update(TINY, **override)
    name = f"{config}.{traffic}"
    pick = lambda ms: [m for m in ms  # noqa: E731
                       if name in m.get("workloads", [name])]
    return harness.Cell(name, config, cfg,
                        loadgen.load_mix(ROOT / "bench" / "traffic"
                                         / f"{traffic}.json"), 1,
                        pick(spec["end_to_end"]), pick(spec["per_layer"]))


@pytest.fixture
def cell_of():
    return tiny_cell
