"""Cells, configurations, jobs and metrics are found by name."""

import json
import shutil

import pytest

from bench import run
from bench.run import ROOT, load_cell, load_reader


SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads(cell):
    loaded = load_cell(cell)
    config, job = loaded["config"], loaded["job"]
    assert config["name"] == loaded["cell"]["config"]
    assert set(config["fields"]) == {"p_prev", "p_cur", "vel2"}
    assert set(config["limits"]) == {"p_prev", "p_cur"}
    z, y, x = job["shape"]
    assert z % job["ndiv"] == 0 and y % 4 == 0 and x % 4 == 0
    names = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert loaded["per_layer"]
    for m in loaded["per_layer"]:
        assert callable(load_reader(m["name"]))


def test_every_configuration_file_is_named():
    for c in SPEC["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]


def test_a_new_cell_job_and_metric_are_found_by_name(tmp_path):
    """Adding a cell needs only new files and entries."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    job = json.loads((ROOT / "bench/jobs/stream-1152.json").read_text())
    job["shape"] = [576, 1152, 1152]
    (tmp_path / "bench/jobs/stream-576.json").write_text(json.dumps(job))
    (tmp_path / "bench/metrics/rounds_in_window.py").write_text(
        "def read(record):\n    return record['rounds']\n")
    spec["workloads"].append({
        "name": "code1.stream-576", "config": "acoustic8-code1",
        "traffic": "stream-576", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "rounds_in_window", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "gpts_per_s", "workloads": ["code1.stream-576"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = load_cell("code1.stream-576", root=tmp_path)
    assert loaded["job"]["shape"] == [576, 1152, 1152]
    assert loaded["config"]["name"] == "acoustic8-code1"
    assert [m["name"] for m in loaded["per_layer"]] == ["rounds_in_window"]
    assert load_reader("rounds_in_window", root=tmp_path)({"rounds": 3}) == 3


def test_unknown_cell_is_refused():
    with pytest.raises(run.Refused):
        load_cell("no-such-cell")


def test_check_region_is_seeded_and_on_the_grid():
    job = load_cell("code4.stream-1152")["job"]
    a = run.check_region(job, 2**31 + 5)
    assert a == run.check_region(job, 2**31 + 5)
    (z0, z1), (y0, y1), (x0, x1) = a
    assert z1 - z0 == 32 and y1 - y0 == 128 and x1 - x0 == 128
    assert all(v % 4 == 0 for v in (z0, z1, y0, y1, x0, x1))
    edge = (z0 + z1) // 2
    assert edge % 144 == 0 and 288 <= edge <= 864
    seen = {run.check_region(job, s) for s in range(40)}
    assert len(seen) > 10
