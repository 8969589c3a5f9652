"""A tiny copy of a cell for tests on the CPU: WRN-10-1 on 16x16 images,
4 clients of 120 samples, 20 PCA components, 3 clusters per class, with the
cell's own limits. Written under a temporary root beside the real data
files, so the harness runs on it unchanged."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def make_root(tmp: Path, cell: str = "wrn40-paper") -> Path:
    root = tmp / "root"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "workloads").mkdir()
    (root / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    shutil.copytree(BENCH / "families", root / "bench" / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", root / "bench" / "peaks.json")
    w = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    cfg["model"].update(depth=10, image_size=16)
    cfg["fl"].update(pca_components=20, clusters_per_class=3,
                     kmeans_iters=5, local_batch_size=20)
    tr = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    tr.update(train_samples=600, num_clients=4, samples_per_client=120)
    tr.pop("clients_per_round", None)
    cfg["fl"]["clients_per_round"] = 2
    w.update(name="tiny", config="tiny", traffic="tiny")
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(tr))
    (root / "bench" / "workloads" / "tiny.json").write_text(json.dumps(w))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [dict(name="tiny", config="tiny", traffic="tiny",
                               chips=1, why="a test's tiny cell")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
