"""Every name in BENCHMARK.json resolves to its files, the files hold what
the harness reads, and the matrix generators copied into the benchmark
give the program's own matrices."""
import json
import re

import numpy as np
import pytest

from bench import harness
from bench.generators import ic0_poisson2d, lung2_like

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = harness.load_cell(cell, BENCH)
    harness.load_module("generators", c.config["generator"])
    harness.load_module("drivers", c.traffic["driver"])
    assert c.limits and all(v > 0 for v in c.limits.values())
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_names_itself(config):
    data = json.loads((harness.ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["reduced"] == config["reduced"]
    assert data["source"] == config["source"]


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_the_sizes_it_generates(config):
    """Every size the file states is what its generator makes at full size,
    and every key ``reduced`` lists is in the file."""
    from bench.reference import _levels

    data = json.loads((harness.ROOT / config["file"]).read_text())
    assert all(k in data for k in data["reduced"])
    mats = harness.load_module("generators", data["generator"]).make(data, 1)
    L = mats["L"]
    assert L.n == data["n"]
    assert L.nnz == data.get("nnz", data.get("nnz_L"))
    if "nnz_A" in data:
        assert mats["A"].nnz == data["nnz_A"]
    if "levels" in data:
        assert int(_levels(L, False).max()) + 1 == data["levels"]


def test_per_layer_metrics_name_cells_that_report_what_they_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", BENCH)
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "no_such_metric")


@pytest.mark.parametrize("scale", [0.01, 0.05])
def test_lung2_pattern_is_the_programs(scale):
    from repro.sparse import lung2_like as program_lung2

    ours = lung2_like.pattern(scale, 29, 3770, 16, 0)
    theirs = program_lung2(scale=scale, seed=0)
    assert np.array_equal(ours.indptr, theirs.indptr)
    assert np.array_equal(ours.indices, theirs.indices)


@pytest.mark.parametrize("shift", [0.0, 0.05])
@pytest.mark.parametrize("nx,ny", [(7, 5), (24, 24)])
def test_ic0_poisson2d_is_the_programs(nx, ny, shift):
    from repro.sparse import ic0_factor, poisson2d

    A = ic0_poisson2d.poisson2d(nx, ny, np.float32)
    L = ic0_poisson2d.ic0(nx, ny, shift, np.float32)
    A0 = poisson2d(nx, ny, dtype=np.float32)
    L0 = ic0_factor(A0, shift=shift)
    for ours, theirs in ((A, A0), (L, L0)):
        assert np.array_equal(ours.indptr, theirs.indptr)
        assert np.array_equal(ours.indices, theirs.indices)
        assert np.array_equal(ours.data, theirs.data)


def test_values_follow_the_seed_and_the_pattern_does_not():
    cfg = dict(scale=0.01, fat_levels=4, fat_rows=3770, thin_run=16,
               structure_seed=0, diag_low=4.0, offdiag_std=0.25,
               dtype="float32")
    a, b, c = (lung2_like.make(cfg, s)["L"] for s in (5, 5, 2**33 + 1))
    assert np.array_equal(a.indices, c.indices)
    assert np.array_equal(a.data, b.data) and not np.array_equal(a.data, c.data)
