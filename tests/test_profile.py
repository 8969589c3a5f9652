"""Profiling-layer tests: ``profiled_jit`` under the tracer's compile
spans (one backend compile per signature, static args split, nothing for
a call inlined into an outer trace, tracer-off no-op), its offline cost
record, the bench run-registry writer
(``write_bench`` -> BENCH json + history JSONL), the noise-aware
regression gate on synthetic trajectories (in-noise pass, injected
regression, claim flip, empty-history bootstrap) and the
``python -m repro.obs regress`` CLI exit codes."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import profile, registry
from repro.obs.__main__ import main as obs_cli
from repro.obs.profile import CostRecord, profiled_jit


@profiled_jit(name="mm_test", static_argnames=("scale",))
def _mm(a, b, scale=1.0):
    return scale * (a @ b)


def _arrays(n=32, m=16):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.normal(size=(n, m)), jnp.float32),
            jnp.asarray(rng.normal(size=(m, n)), jnp.float32))


# ---------------------------------------------------------------- profiled_jit

def _backend_spans(tr, fn):
    return [s for s in tr.spans if s.name == "compile"
            and s.attrs["phase"] == "backend" and s.attrs["fn"] == fn]


class TestProfiledJit:
    def test_one_backend_compile_span_per_signature(self):
        def mm_once(a, b):
            return a @ b

        f = profiled_jit(mm_once)
        a, b = _arrays()
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            with obs.span("select"):
                f(a, b)
                f(a, b)                     # cached: no compile
        sel = next(s for s in tr.spans if s.name == "select")
        spans = _backend_spans(tr, "jit(mm_once)")
        # one compile, not a second one for a cost record
        assert len(spans) == 1
        assert spans[0].parent_id == sel.span_id
        assert sel.t0 <= spans[0].t0 <= spans[0].t1 <= sel.t1
        assert "flops" not in sel.attrs and "utilization" not in sel.attrs

    def test_sentinel_counts_each_signature_once(self):
        def poly(x):
            return x * 2

        f = profiled_jit(poly)
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            for n in (4, 8, 16):            # three shapes = three compiles
                f(jnp.zeros((n,), jnp.float32))
            for n in (4, 8, 16):            # repeats: no new compiles
                f(jnp.zeros((n,), jnp.float32))
        counters = tr.metrics.snapshot()["counters"]
        assert counters["compile.jit(poly)"] == 3
        assert len(_backend_spans(tr, "jit(poly)")) == 3
        assert counters["compile_s.backend"] > 0

    def test_static_argnames_split_signature(self):
        @profiled_jit(name="mm_static", static_argnames=("scale",))
        def g(a, b, scale=1.0):
            return scale * (a @ b)

        a, b = _arrays()
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            g(a, b, scale=1.0)
            g(a, b, scale=2.0)              # new static value -> recompile
            g(a, b, scale=2.0)              # cached
        assert tr.metrics.snapshot()["counters"]["compile.jit(g)"] == 2
        assert len(_backend_spans(tr, "jit(g)")) == 2

    def test_disabled_tracer_is_plain_jit(self):
        a, b = _arrays()
        out = _mm(a, b)
        assert out.shape == (32, 32)        # no tracer: must not raise

    def test_inside_jax_trace_falls_back(self):
        import jax

        def inner_fb(x):
            return x + 1

        def outer_fb(x):
            return f(x) * 2

        f = profiled_jit(inner_fb)
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            jax.jit(outer_fb)(jnp.zeros((3, 4), jnp.float32))
        # the inner call inlines into the outer trace: its compile belongs
        # to the outer function
        counters = tr.metrics.snapshot()["counters"]
        assert "compile.jit(inner_fb)" not in counters
        assert counters["compile.jit(outer_fb)"] == 1

    def test_cost_offline(self):
        a, b = _arrays()
        cost = _mm.cost(a, b)
        assert isinstance(cost, CostRecord)
        assert cost.flops >= 2 * 32 * 16 * 32
        assert cost.hbm_bytes > 0

    def test_roofline_terms(self):
        cost = CostRecord(flops=1e12, hbm_bytes=1e9, collective_bytes=0.0)
        peaks = profile.peak_table("cpu")
        terms = profile.roofline(cost, peaks)
        assert set(terms) == {"compute_s", "memory_s", "collective_s",
                              "bound"}
        assert terms["bound"] in ("compute", "memory", "collective")

    def test_peak_table_v5e_from_mesh_constants(self):
        from repro.launch import mesh
        peaks = profile.peak_table(mesh.V5E_DEVICE_KIND)
        assert peaks["peak_flops_bf16"] == mesh.PEAK_FLOPS_BF16
        assert peaks["hbm_bw"] == mesh.HBM_BW
        assert peaks["ici_bw"] == mesh.ICI_BW

    @pytest.mark.parametrize("kind", ["tpu", "TPU v4", "TPU v6 lite", ""])
    def test_peak_table_unknown_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no peak table"):
            profile.peak_table(kind)

    def test_record_from_dryrun_roundtrip(self):
        rec = {"cost": {"flops_expanded": 5.0, "bytes_expanded": 7.0},
               "collectives": {"total_bytes": 3.0,
                               "unknown_trip_counts": 1}}
        c = profile.record_from_dryrun(rec)
        assert (c.flops, c.hbm_bytes, c.collective_bytes,
                c.unknown_trip_loops) == (5.0, 7.0, 3.0, 1)


# ---------------------------------------------------------------- registry

def _report(overhead=0.02, rps=1e5, ok=True):
    return {"overhead_frac": overhead, "records_per_sec": rps,
            "nested": {"traced_s": 1.0 + overhead},
            "pairs": [1, 2, 3],
            "claims": {"overhead_leq_3pct": ok}}


class TestRegistry:
    def test_write_bench_writes_json_and_history(self, tmp_path):
        bench = tmp_path / "BENCH_demo.json"
        rec = registry.write_bench(str(bench), _report())
        assert json.loads(bench.read_text())["overhead_frac"] == 0.02
        hist = registry.load_history(
            str(tmp_path / "experiments" / "bench_history.jsonl"))
        assert len(hist) == 1
        assert hist[0]["bench"] == "demo" == rec["bench"]
        assert hist[0]["schema"] == registry.SCHEMA
        assert "git_rev" in hist[0]["fingerprint"]
        assert hist[0]["scalars"]["nested.traced_s"] == pytest.approx(1.02)
        assert hist[0]["claims"] == {"overhead_leq_3pct": True}

    def test_history_appends(self, tmp_path):
        bench = tmp_path / "BENCH_demo.json"
        for _ in range(3):
            registry.write_bench(str(bench), _report())
        hpath = tmp_path / "experiments" / "bench_history.jsonl"
        assert len(registry.load_history(str(hpath))) == 3

    def test_flatten_scalars_skips_bools_and_lists(self):
        flat = registry.flatten_scalars(_report())
        assert "claims.overhead_leq_3pct" not in flat
        assert "pairs" not in flat
        assert flat["overhead_frac"] == 0.02

    def test_load_history_missing_is_empty(self, tmp_path):
        assert registry.load_history(str(tmp_path / "nope.jsonl")) == []

    def test_load_history_malformed_raises(self, tmp_path):
        p = tmp_path / "h.jsonl"
        p.write_text('{"ok": 1}\nnot json\n')
        with pytest.raises(ValueError):
            registry.load_history(str(p))

    def test_bench_name(self):
        assert registry.bench_name("/x/BENCH_selection.json") == "selection"
        assert registry.bench_name("/x/results.json") is None


# ---------------------------------------------------------------- regress gate

def _history(n, overhead=0.02, rps=1e5, jitter=0.001, ok=True):
    return [registry.history_record(
        "demo", _report(overhead + jitter * ((i % 3) - 1),
                        rps * (1 + 0.01 * ((i % 3) - 1)), ok=ok))
        for i in range(n)]


class TestRegressGate:
    def test_in_noise_passes(self):
        rep = registry.regress_report("demo", _report(0.021), _history(6))
        assert rep["failures"] == []
        assert rep["checked"] > 0

    def test_injected_regression_fails_high_bad(self):
        rep = registry.regress_report("demo", _report(overhead=0.5),
                                      _history(6))
        assert any("overhead_frac" in f for f in rep["failures"])

    def test_injected_regression_fails_low_bad(self):
        rep = registry.regress_report("demo", _report(rps=10.0), _history(6))
        assert any("records_per_sec" in f for f in rep["failures"])

    def test_improvement_never_fails(self):
        rep = registry.regress_report(
            "demo", _report(overhead=0.0001, rps=1e9), _history(6))
        assert rep["failures"] == []

    def test_claim_flip_hard_fails(self):
        rep = registry.regress_report("demo", _report(ok=False), _history(6))
        assert any("flipped FALSE" in f for f in rep["failures"])

    def test_claim_never_true_does_not_fail(self):
        rep = registry.regress_report("demo", _report(ok=False),
                                      _history(6, ok=False))
        assert not any("flipped" in f for f in rep["failures"])

    def test_empty_history_bootstraps(self):
        rep = registry.regress_report("demo", _report(), [])
        assert rep["failures"] == []
        assert rep["checked"] == 0
        assert any("bootstrap" in n for n in rep["notes"])

    def test_min_history_gates_nothing_below_threshold(self):
        rep = registry.regress_report("demo", _report(overhead=9.9),
                                      _history(2))
        assert rep["failures"] == []   # 2 < min_history=3: ungated

    def test_other_bench_history_ignored(self):
        other = _history(6)
        for r in other:
            r["bench"] = "unrelated"
        rep = registry.regress_report("demo", _report(overhead=9.9), other)
        assert rep["failures"] == [] and rep["history_points"] == 0


# ---------------------------------------------------------------- regress CLI

def _seed_cli(tmp_path, n=4, **kw):
    bench = tmp_path / "BENCH_demo.json"
    hpath = tmp_path / "experiments" / "bench_history.jsonl"
    hpath.parent.mkdir()
    with hpath.open("w") as f:
        for r in _history(n):
            f.write(json.dumps(r) + "\n")
    bench.write_text(json.dumps(_report(**kw)))
    return str(bench), str(hpath)


class TestRegressCLI:
    def test_clean_exits_zero(self, tmp_path, capsys):
        bench, hist = _seed_cli(tmp_path)
        assert obs_cli(["regress", bench, "--history", hist]) == 0
        assert "demo" in capsys.readouterr().out

    def test_regression_exits_one(self, tmp_path, capsys):
        bench, hist = _seed_cli(tmp_path, overhead=5.0)
        assert obs_cli(["regress", bench, "--history", hist]) == 1
        assert "FAIL" in capsys.readouterr().out

    def test_missing_bench_exits_two(self, tmp_path):
        _, hist = _seed_cli(tmp_path)
        assert obs_cli(["regress", str(tmp_path / "BENCH_gone.json"),
                        "--history", hist]) == 2

    def test_non_bench_filename_exits_two(self, tmp_path):
        bench, hist = _seed_cli(tmp_path)
        other = tmp_path / "results.json"
        other.write_text("{}")
        assert obs_cli(["regress", str(other), "--history", hist]) == 2

    def test_malformed_history_exits_two(self, tmp_path):
        bench, hist = _seed_cli(tmp_path)
        with open(hist, "a") as f:
            f.write("not json\n")
        assert obs_cli(["regress", bench, "--history", hist]) == 2

    def test_missing_history_bootstraps_zero(self, tmp_path):
        bench, _ = _seed_cli(tmp_path)
        assert obs_cli(["regress", bench, "--history",
                        str(tmp_path / "none.jsonl")]) == 0
