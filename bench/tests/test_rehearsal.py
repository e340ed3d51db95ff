"""CPU rehearsals: every traffic kind end to end through the harness's
driver functions at a tiny size, the controls coming out not correct, and
the harness refusing to run without a TPU."""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench.tests.rehearsal import ROOT, rehearse

BUILD = {"pool_batches": 2}


@pytest.mark.parametrize("cell,traffic", [
    ("rmat-ic-wc.build", BUILD),
    ("rmat-ic-uni.build", BUILD),
])
def test_cell_rehearses_correct(cell, traffic):
    line, run = rehearse(cell, traffic=traffic)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
    assert "setup_s" in line["metrics"]
    assert line["device"]["count"] == run.cell["chips"]


@pytest.mark.parametrize("cell,control", [
    ("rmat-ic-wc.build", "bf16"),
    ("rmat-ic-uni.build", "bf16"),
    ("rmat-ic-wc.build", "cap8"),
])
def test_build_controls_fail(cell, control):
    """Probabilities one precision below float32, and a level cap that
    cuts sets short (as the program's default cap of 64 does at full
    size), both fail the comparison."""
    line, _ = rehearse(cell, traffic=dict(BUILD, check_edge_budget=10**9),
                       control=control)
    assert not line["correct"]
    assert line["checks"]["sets_differing"]["value"] > 0


def test_cap_above_census_depth_is_correct():
    """The cap only matters where a set is deeper than it."""
    line, _ = rehearse("rmat-ic-wc.build", traffic=BUILD, control="cap200")
    assert line["correct"], line["checks"]


def test_no_chip_no_result():
    """Under the CPU the harness exits non-zero and prints nothing on
    standard output."""
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "rmat-ic-wc.build", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_work")})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_outside_checkout_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rmat-ic-wc.build",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_names_its_files():
    """Every cell, configuration, traffic and metric is found by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]
    for w in bench["workloads"]:
        traffic = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        kind = json.loads(traffic.read_text())["kind"]
        assert (ROOT / "bench" / "drivers" / f"{kind}.py").is_file()
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
