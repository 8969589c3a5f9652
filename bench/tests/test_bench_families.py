"""A configuration's model family is found by name: a family that exists
only as new files runs through the harness as the WRN family does, and a
configuration that names no family, or one with no module, is refused
before the run looks for a chip."""
import json
import shutil
import subprocess
import sys

import pytest

from tiny import BENCH, make_root
import run
import spec

SEED = 2**31 + 5


def _measure(cell, root):
    return run.measure(cell, SEED, 0.01, False, require_tpu=False, root=root,
                       cache=False)


def test_new_family_as_new_files(tmp_path):
    """A copy of the WRN family under a name the harness has never seen,
    with a configuration, a traffic mix and a cell of its own, all new
    files: the run is correct and reads what the tiny WRN cell reads."""
    root = make_root(tmp_path)
    bdir = root / "bench"
    shutil.copy(bdir / "families" / "wrn.py", bdir / "families" / "copied.py")
    cfg = json.loads((bdir / "configs" / "tiny.json").read_text())
    cfg.update(name="copied-tiny", family="copied")
    (bdir / "configs" / "copied-tiny.json").write_text(json.dumps(cfg))
    shutil.copy(bdir / "traffic" / "tiny.json",
                bdir / "traffic" / "copied-tiny.json")
    w = json.loads((bdir / "workloads" / "tiny.json").read_text())
    w.update(name="copied-tiny", config="copied-tiny", traffic="copied-tiny")
    (bdir / "workloads" / "copied-tiny.json").write_text(json.dumps(w))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(bench["workloads"][0], name="copied-tiny",
                                   config="copied-tiny",
                                   traffic="copied-tiny"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("copied-tiny", root)
    assert cell.family == "copied"
    assert spec.load_family("copied", root).__file__ == str(
        bdir / "families" / "copied.py")
    new = _measure(cell, root)
    old = _measure(spec.load_cell("tiny", root), root)
    assert new["correct"], new["checked"]
    assert (new["metrics"]["uplink_bytes_per_update"]
            == old["metrics"]["uplink_bytes_per_update"])
    assert new["checked"] == old["checked"]


@pytest.mark.parametrize("family,message", [
    ("nosuch", "families/nosuch.py"), (None, "names no family")])
def test_config_without_a_family_module_exits_2(tmp_path, family, message):
    root = make_root(tmp_path)
    for name in ("run.py", "spec.py"):
        shutil.copy(BENCH / name, root / "bench" / name)
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    p = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", "tiny",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert p.stderr.startswith("bench: ") and message in p.stderr


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_config_names_a_family_with_a_module(path):
    family = json.loads(path.read_text())["family"]
    assert (BENCH / "families" / f"{family}.py").is_file()
    module = spec.load_family(family)
    assert all(hasattr(module, name) for name in spec.FAMILY_API)
