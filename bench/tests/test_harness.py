"""The harness end to end on the CPU: refusals, seeded traffic, and the
per-layer readers on a traced run."""
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, lanes, loadgen, trace_reduce

ROOT = Path(__file__).resolve().parents[2]


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid2d_256.direct",
         "--seed", "3", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run_py(ROOT)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_every_cell_resolves():
    import json
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        g = harness.make_graph(cell.config)
        assert (g.n, g.m) == (cell.config["n"], cell.config["m"])
        for m in cell.end_to_end + cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_generators_match_the_program():
    from repro.data import graphs
    from bench.generators import grid2d, grid3d
    a, b = grid2d.generate(20, 16, 11), graphs.grid2d(20, 16, seed=11)
    assert a[0] == b.n and all(np.array_equal(x, y) for x, y in
                               zip(a[1:], (b.src, b.dst, b.w)))
    a = grid3d.generate(6, 5, 4, "contrast", 13)
    b = graphs.grid3d(6, 5, 4, "contrast", seed=13)
    assert all(np.array_equal(x, y) for x, y in
               zip(a[1:], (b.src, b.dst, b.w)))


def test_traffic_is_seeded():
    mix = {"path": "served", "loop": "open", "clients": 0,
           "columns": [1, 4], "rate_per_s": 5.0}
    big = 2 ** 31 + 11
    b1, b2 = (loadgen.request_rhs(mix, big, 50, 2, 7) for _ in range(2))
    assert np.array_equal(b1, b2) and abs(float(np.mean(b1))) < 1e-6
    assert not np.array_equal(b1, loadgen.request_rhs(mix, big + 1, 50, 2, 7))
    assert loadgen.arrivals(mix, big, 10.0) == loadgen.arrivals(mix, big,
                                                                10.0)
    with pytest.raises(ValueError):
        loadgen.load_mix(ROOT / "BENCHMARK.json")


OPEN = {"path": "served", "loop": "open", "rate_per_s": 40.0,
        "columns": [1, 2]}


@pytest.mark.parametrize("mix", [None, OPEN])
def test_served_run_counts_passes(cell_of, mix):
    """Closed loop (the ``lanes8`` mix) and an open loop of one- and
    two-column requests: every answer checks, and the passes counted
    from the answers account for every iteration."""
    cell = cell_of("lanes8")
    if mix is not None:
        cell.mix = loadgen.load_mix_dict(mix)
    run = harness.run_cell(cell, 2 ** 31 + 9, 2.0, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           log=lambda _: None)
    assert harness.passed(harness.check(run))
    k = int(cell.config["iters_per_tick"])
    per = lanes.tick_advances(run.sent, k)
    assert per is not None
    assert sum(map(sum, per.values())) == sum(int(np.sum(r.iters))
                                              for r in run.sent)
    got = harness.read_metrics(run, cell.end_to_end)
    assert set(got) == {"rhs_per_s", "latency_p95_s", "cold_start_s",
                        "setup_s"}


@pytest.mark.parametrize("traffic", ["direct", "lanes8"])
def test_traced_run_reads_per_layer_metrics(cell_of, monkeypatch, traffic):
    """With the CPU's op lines read as the device's, a traced run yields
    every per-layer metric of its cell."""
    monkeypatch.setattr(trace_reduce, "is_device_op_line",
                        lambda plane, line: plane == "/host:CPU"
                        and line.startswith("tf_XLA"))
    monkeypatch.setattr(harness, "SEGMENT_S", 0.3)
    cell = cell_of(traffic)
    run = harness.run_cell(cell, 2 ** 31 + 13, 2.0, True,
                           t_start=time.perf_counter(), require_tpu=False,
                           log=lambda _: None)
    run.device_kind = "TPU v5 lite"      # the peaks of the chip it stands for
    got = harness.read_metrics(run, cell.per_layer)
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert 0.0 <= got["device_idle_share"]["value"] < 1.0
    assert got["pcg_iter_ms"]["value"] > 0
    assert got["construct_device_s"]["value"] > 0


def test_compiling_cold_start_stops_when_asked(cell_of):
    """A first run's cold start that compiles stops there when asked
    (a fresh process then measures it from the persistent cache), and
    leaves no engine thread behind."""
    import threading
    cell = cell_of("lanes8", params={"nx": 22, "ny": 22, "seed": 11},
                   n=484, m=924)
    threads = threading.active_count()
    with pytest.raises(harness.ColdStartCompiled, match="compiled"):
        harness.run_cell(cell, 2 ** 31 + 29, 1.0, False,
                         t_start=time.perf_counter(), require_tpu=False,
                         exit_if_compiled=True, log=lambda _: None)
    assert threading.active_count() == threads
