"""BENCHMARK.json resolves by name to its files, its names keep to the
contract's characters, traffic is set by the seed, and the harness refuses
to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import _paths
import numpy as np
import pytest

from benchlib.generator import Pool, draw_sizes

BENCH = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_its_files(cell):
    run = _paths.bench_run()
    c = run.Cell.of_benchmark(BENCH, cell["name"])
    assert c.traffic["driver"] and (_paths.BENCH / "drivers" /
                                    f"{c.traffic['driver']}.py").is_file()
    assert set(c.limits) == {"loss_rel", "grad_norm_gap", "update_norm_gap"}
    for m in c.per_layer:
        assert (_paths.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "atoms_per_s"}


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_names_what_it_reduced(config):
    path = _paths.ROOT / config["file"]
    assert path.is_file() and path.parts[-3] == "bench"
    body = json.loads(path.read_text())
    for key in config["reduced"]:
        assert key in body and f"source_{key}" in body
        assert body[key] != body[f"source_{key}"]


def test_names_and_units_use_the_allowed_characters():
    names = [m["name"] for m in _metrics()] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [c["name"] for c in BENCH["configs"]] + \
        [w["traffic"] for w in BENCH["workloads"]] + \
        [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.fullmatch(n), n
    for m in _metrics():
        assert UNIT.fullmatch(m["unit"]), m["unit"]
    assert len({m["name"] for m in _metrics()}) == len(_metrics())


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


def test_traffic_is_fixed_by_the_seed():
    traffic = json.loads((_paths.BENCH / "traffic" / "table3.json").read_text())
    small = dict(traffic, graphs=40)
    a, b, c = (Pool(small, 4.5, s) for s in (2**33 + 1, 2**33 + 1, 2**33 + 2))
    for x, y in zip(a.structures, b.structures):
        np.testing.assert_array_equal(x.positions, y.positions)
        np.testing.assert_array_equal(x.senders, y.senders)
    assert any(x.n_atoms != y.n_atoms or not np.array_equal(x.positions, y.positions)
               for x, y in zip(a.structures, c.structures))
    # every seed trains on the same multiset of sizes, in its own order
    assert sorted(a.sizes) == sorted(c.sizes)
    assert list(a.sizes) != list(c.sizes)


def test_harness_exits_without_a_result_when_there_is_no_tpu():
    cell = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(_paths.BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=_paths.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no chip" in p.stderr


def test_a_size_histogram_sets_the_sizes():
    hist = [[1, 4, 0.5], [100, 120, 0.5]]
    mixture = [["MPtrj", 1.0, 1, 444, "lattice", 1.0, hist]]
    _, sizes = draw_sizes(mixture, 4000, size_seed=2**33 + 3)
    low = (sizes >= 1) & (sizes <= 4)
    high = (sizes >= 100) & (sizes <= 120)
    assert np.all(low | high)
    assert abs(low.mean() - 0.5) < 0.05
    assert set(np.unique(sizes[low])) == {1, 2, 3, 4}
