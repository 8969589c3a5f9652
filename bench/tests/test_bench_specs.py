"""Every cell names a configuration, a traffic mix and metrics that exist,
and BENCHMARK.json agrees with the files."""
import json
import subprocess
import sys

import pytest

from tiny import BENCH, ROOT
import spec

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


def test_every_declared_cell_has_a_file():
    names = [w["name"] for w in BENCHMARK["workloads"]]
    assert names and set(names) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files(name):
    w = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    assert w["name"] == name
    assert (BENCH / "configs" / f"{w['config']}.json").is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert w["chips"] in (1, 4) and w["why"]
    assert w["limits"] and w["check_rounds"] >= 1
    declared = [d for d in BENCHMARK["workloads"] if d["name"] == name]
    if declared:
        d = declared[0]
        assert (d["config"], d["traffic"], d["chips"]) == (
            w["config"], w["traffic"], w["chips"])


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_metrics_have_readers(name):
    cell = spec.load_cell(name)
    assert {m.name for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_reader(m.name))


def test_configs_declared_match_files():
    for c in BENCHMARK["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert set(c["reduced"]) == set(data["reduced"])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v0 imaginary")
    assert spec.load_peaks("TPU v5 lite")["flops_bf16"] == 197e12


def test_run_fails_without_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr
