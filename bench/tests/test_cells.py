"""Cells, configurations, jobs and metrics are found by name."""

import json
import os
import shutil
import subprocess
import sys

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


HALO_READER = """def read(record):
    t, steps = record.get("transfers"), record.get("steps", 0)
    if not t or not t.get("halo_wire") or not steps:
        return None
    return t["halo_wire"] / steps / 1e9
"""

# a traced sharded run at the tiny size, in the copied tree, with the
# copied harness: prints the metrics of the result
SHARDED_RUN = """
import json, sys
sys.path.insert(0, sys.argv[1])
from bench import run
loaded = run.load_cell("code4.sharded4-1152")
loaded["job"].update(shape=[192, 32, 128], ndiv=8, region=[16, 16, 16])
got = run.run_cell(loaded, 2**31 + 17, 0.05, True, allow_cpu=True)
print(json.dumps({"correct": got["correct"], "metrics": got["metrics"]}))
"""


def test_a_new_cell_job_and_metric_are_found_by_name(tmp_path):
    """Adding a cell needs only new files and entries: a one-chip
    stream cell, and a four-chip sharded cell whose halo reader reads
    the window's halo bytes, run in the copied tree."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    (tmp_path / "src").symlink_to(ROOT / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    job = json.loads((ROOT / "bench/jobs/stream-1152.json").read_text())
    job["shape"] = [576, 1152, 1152]
    (tmp_path / "bench/jobs/stream-576.json").write_text(json.dumps(job))
    (tmp_path / "bench/metrics/rounds_in_window.py").write_text(
        "def read(record):\n    return record['rounds']\n")
    job = json.loads((ROOT / "bench/jobs/stream-1152.json").read_text())
    job["shards"] = 4
    (tmp_path / "bench/jobs/sharded4-1152.json").write_text(json.dumps(job))
    (tmp_path / "bench/metrics/halo_gb_per_step.py").write_text(HALO_READER)
    spec["workloads"] += [
        {"name": "code1.stream-576", "config": "acoustic8-code1",
         "traffic": "stream-576", "chips": 1, "why": "test"},
        {"name": "code4.sharded4-1152", "config": "acoustic8-code4",
         "traffic": "sharded4-1152", "chips": 4, "why": "test"}]
    spec["per_layer"] += [
        {"name": "rounds_in_window", "unit": "rounds", "better": "higher",
         "source": "program_counter", "layer": "device",
         "moves": "gpts_per_s", "workloads": ["code1.stream-576"]},
        {"name": "halo_gb_per_step", "unit": "GB/step", "better": "lower",
         "source": "program_counter", "layer": "host store and link",
         "moves": "gpts_per_s", "workloads": ["code4.sharded4-1152"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = load_cell("code1.stream-576", root=tmp_path)
    assert loaded["job"]["shape"] == [576, 1152, 1152]
    assert loaded["config"]["name"] == "acoustic8-code1"
    assert [m["name"] for m in loaded["per_layer"]] == ["rounds_in_window"]
    assert load_reader("rounds_in_window", root=tmp_path)({"rounds": 3}) == 3

    loaded = load_cell("code4.sharded4-1152", root=tmp_path)
    assert loaded["cell"]["chips"] == loaded["job"]["shards"] == 4
    assert "halo_gb_per_step" in [m["name"] for m in loaded["per_layer"]]
    read = load_reader("halo_gb_per_step", root=tmp_path)
    assert read({"transfers": {"halo_wire": 3e9}, "steps": 2}) == 1.5
    assert read({"transfers": {"halo_wire": 0}, "steps": 2}) is None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SHARDED_RUN, str(tmp_path)],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert got["correct"]
    assert got["metrics"]["halo_gb_per_step"]["value"] > 0


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


# the regions the check drew for each seed 0..39 before jobs could be
# sharded, as (z0, y0, x0) of a 32x128x128 box
REGIONS = {
    "code4.stream-1152": [
        (704, 332, 404), (704, 456, 520), (704, 364, 652), (560, 584, 692),
        (848, 696, 620), (416, 516, 648), (704, 384, 344), (272, 384, 476),
        (704, 368, 612), (560, 572, 692), (416, 544, 504), (704, 532, 592),
        (416, 472, 440), (704, 500, 364), (272, 416, 616), (704, 416, 588),
        (272, 664, 636), (416, 380, 556), (416, 544, 500), (272, 412, 688),
        (272, 612, 616), (848, 488, 408), (848, 592, 552), (272, 496, 624),
        (704, 328, 464), (560, 616, 444), (560, 440, 328), (704, 644, 544),
        (272, 508, 564), (704, 540, 488), (560, 640, 596), (272, 332, 644),
        (704, 420, 612), (416, 328, 356), (272, 648, 564), (560, 692, 576),
        (848, 564, 492), (416, 660, 668), (848, 612, 344), (848, 660, 480),
    ],
    "code1.resident-384": [
        (240, 332, 404), (240, 456, 520), (240, 364, 652), (240, 584, 692),
        (240, 696, 620), (112, 516, 648), (240, 384, 344), (112, 384, 476),
        (240, 368, 612), (240, 572, 692), (112, 544, 504), (240, 532, 592),
        (112, 472, 440), (240, 500, 364), (112, 416, 616), (240, 416, 588),
        (112, 664, 636), (112, 380, 556), (112, 544, 500), (112, 412, 688),
        (112, 612, 616), (240, 488, 408), (240, 592, 552), (112, 496, 624),
        (240, 328, 464), (112, 616, 444), (240, 440, 328), (240, 644, 544),
        (112, 508, 564), (240, 540, 488), (240, 640, 596), (112, 332, 644),
        (240, 420, 612), (112, 328, 356), (112, 648, 564), (112, 692, 576),
        (240, 564, 492), (112, 660, 668), (240, 612, 344), (240, 660, 480),
    ],
}


@pytest.mark.parametrize("cell", sorted(REGIONS))
def test_a_job_without_shards_draws_the_same_region_as_before(cell):
    job = load_cell(cell)["job"]
    assert "shards" not in job
    assert [run.check_region(job, s) for s in range(40)] == [
        ((z, z + 32), (y, y + 128), (x, x + 128)) for z, y, x in REGIONS[cell]]


def test_a_sharded_region_straddles_a_shard_boundary():
    from repro.distributed.sharding import partition_domain

    job = dict(load_cell("code4.stream-1152")["job"], shards=4)
    block = job["shape"][0] // job["ndiv"]
    bounds = {s.block_lo * block for s in partition_domain(job["ndiv"], 4)[1:]}
    assert bounds == {288, 576, 864}
    seen = set()
    for s in range(40):
        (z0, z1), (y0, y1), (x0, x1) = run.check_region(job, s)
        assert z1 - z0 == 32 and y1 - y0 == 128 and x1 - x0 == 128
        seen.add((z0 + z1) // 2)
    assert seen == bounds
