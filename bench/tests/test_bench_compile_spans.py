"""The readers of the program's ``compile`` spans, on hand-built span
lists whose answers are known (overlapping compile spans included), and
on a traced run of a tiny cell on the CPU."""
import json

import pytest

from tiny import make_root
import run
import spec

SEED = 2**31 + 11


def _span(sid, name, t0, t1, parent=None, **attrs):
    return {"type": "span", "id": sid, "parent": parent, "name": name,
            "t0": t0, "t1": t1, "attrs": attrs}


def _ctx(spans, rounds=2, compiles=3):
    return {"spans": spans, "rounds": rounds, "compiles": compiles}


# two rounds: meta_train [1, 3] holds a trace [1.0, 1.5] that nests a
# trace [1.1, 1.2], then lower [1.5, 1.7] and backend [1.7, 2.2]; a
# compile under select [5, 5.25]; meta_train [10, 11] holds backend
# [10.5, 10.75]
SPANS = [
    _span(1, "round", 0.0, 4.0, round=1),
    _span(2, "aggregate", 0.9, 3.5, parent=1),
    _span(3, "meta_train", 1.0, 3.0, parent=2),
    _span(4, "compile", 1.0, 1.5, parent=3, phase="trace", fn="scan"),
    _span(5, "compile", 1.1, 1.2, parent=3, phase="trace", fn="add"),
    _span(6, "compile", 1.5, 1.7, parent=3, phase="lower", fn="jit(scan)"),
    _span(7, "compile", 1.7, 2.2, parent=3, phase="backend",
          fn="jit(scan)", cache_hit=True),
    _span(8, "round", 4.5, 12.0, round=2),
    _span(9, "select", 4.6, 6.0, parent=8),
    _span(10, "compile", 5.0, 5.25, parent=9, phase="trace",
          fn="eval_shape"),
    _span(11, "meta_train", 10.0, 11.0, parent=8),
    _span(12, "compile", 10.5, 10.75, parent=11, phase="backend",
          fn="jit(scan)", cache_hit=True),
]


def _read(name, ctx):
    return spec.load_reader(name)(ctx)


def test_compile_s_per_round_is_the_union_over_rounds():
    # union: [1.0, 2.2] + [5.0, 5.25] + [10.5, 10.75] = 1.7 s over 2
    assert _read("compile_s_per_round", _ctx(SPANS)) == pytest.approx(0.85)


def test_meta_train_s_per_round_leaves_out_its_compile_children():
    # meta_train 2 + 1 = 3 s; compile children 1.2 + 0.25 = 1.45 s
    assert _read("meta_train_s_per_round", _ctx(SPANS)) == \
        pytest.approx((3.0 - 1.45) / 2)


def test_parts_account_for_the_meta_train_spans():
    import _compile
    kids = [s for s in SPANS if s["name"] == "compile"
            and s["parent"] in (3, 11)]
    parts = (_read("meta_train_s_per_round", _ctx(SPANS)) * 2
             + _compile.covered_s(kids))
    assert parts == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["compile_s_per_round",
                                  "meta_train_s_per_round"])
def test_no_compile_spans_where_the_harness_counted_compiles(name):
    """A program that records no compile spans (the harness counted
    backend compiles in the window, the trace holds none) reads nothing."""
    spans = [s for s in SPANS if s["name"] != "compile"]
    assert _read(name, _ctx(spans, compiles=2)) is None


def test_a_window_without_compiles_reads_zero():
    spans = [s for s in SPANS if s["name"] != "compile"]
    assert _read("compile_s_per_round", _ctx(spans, compiles=0)) == 0.0
    assert _read("meta_train_s_per_round", _ctx(spans, compiles=0)) == \
        pytest.approx(1.5)


@pytest.mark.parametrize("ctx", [_ctx(SPANS, rounds=0), _ctx([])])
def test_nothing_to_read(ctx):
    assert _read("meta_train_s_per_round", ctx) is None
    if not ctx["rounds"]:
        assert _read("compile_s_per_round", ctx) is None


def test_traced_tiny_run_reports_both(tmp_path, monkeypatch):
    """A ``--trace 1`` run of the tiny cell reports both metrics, and its
    window's backend compile spans are the compiles the harness counted."""
    seen = {}
    real = run.span_records

    def keep(tracer, t0, t1):
        seen["spans"] = real(tracer, t0, t1)
        return seen["spans"]

    monkeypatch.setattr(run, "span_records", keep)
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny", root)
    line = run.measure(cell, SEED, 0.01, True, require_tpu=False, root=root,
                       cache=False)
    assert line["correct"], line["checked"]
    got = line["metrics"]
    assert got["compile_s_per_round"]["value"] > 0
    assert got["meta_train_s_per_round"]["value"] > 0
    backend = [s for s in seen["spans"] if s["name"] == "compile"
               and s["attrs"]["phase"] == "backend"]
    assert len(backend) == got["compiles_in_window"]["value"] > 0
