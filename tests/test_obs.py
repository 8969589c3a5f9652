"""Observability tests: trace JSONL schema round-trip, the diff/summarize
CLI exit codes, bit-identity of traced vs untraced zero-fault runs
(weights AND ledger), selection-sketch regression against
``select_metadata``, chaos-trace fault counters vs channel totals, the
MeteredLedger bridge, a loose tracing-overhead smoke guard, and the
compile spans and profiler annotations of a traced run."""
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import FLConfig, get_wrn_config
from repro.core.selection import select_metadata
from repro.data import SyntheticImageDataset, partition_k_shards
from repro.fl.comms import CommLedger
from repro.fl.faults import FaultPlan
from repro.fl.server import FLServer
from repro.fl.simulation import FLSimulation
from repro.models.wrn import make_split_wrn
from repro.obs.__main__ import main as obs_cli
from repro.obs.timing import Timing, monotonic, timeit

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
NUM_CLASSES, CLUSTERS, ROUNDS = 4, 2, 2
FL_KW = dict(num_clients=3, clients_per_round=3, local_epochs=1,
             local_batch_size=20, local_lr=0.1, pca_components=8,
             clusters_per_class=CLUSTERS, kmeans_iters=4, meta_epochs=2,
             meta_batch_size=8, meta_lr=0.05)


# ------------------------------------------------------------------ units

class TestTiming:
    def test_monotonic_is_monotonic(self):
        a = monotonic()
        b = monotonic()
        assert b >= a

    def test_timeit_returns_timing_and_output(self):
        t = timeit(lambda x: x + 1, 41, iters=3)
        assert isinstance(t, Timing)
        assert t.out == 42 and t.seconds >= 0.0

    def test_timeit_reduce_min_and_errors(self):
        assert timeit(lambda: 7, iters=2, reduce="min").out == 7
        with pytest.raises(ValueError):
            timeit(lambda: 0, iters=0)
        with pytest.raises(ValueError):
            timeit(lambda: 0, reduce="median")


class TestMetrics:
    def test_registry_create_on_first_use(self):
        m = obs.MetricsRegistry()
        m.counter("a").inc()
        m.counter("a").inc(2)
        m.gauge("g").set(1.5)
        m.histogram("h").observe(3.0)
        snap = m.snapshot()
        assert snap["counters"]["a"] == 3
        assert snap["gauges"]["g"] == 1.5
        assert snap["histograms"]["h"]["count"] == 1

    def test_null_metrics_are_inert_singletons(self):
        n = obs.NULL_METRICS
        assert n.counter("x") is n.counter("y")
        n.counter("x").inc(5)
        assert n.counter("x").value == 0
        assert n.snapshot()["counters"] == {}

    def test_metered_ledger_matches_plain_and_mirrors(self):
        plain = CommLedger()
        tr = obs.Tracer()
        metered = obs.MeteredLedger(tr)
        for led in (plain, metered):
            led.upload("metadata", 100)
            led.upload("metadata", 50, frames=2)
            led.download("weights", 400)
        assert metered.summary() == plain.summary()
        snap = tr.metrics.snapshot()["counters"]
        assert snap["ledger.up.metadata.bytes"] == 150
        assert snap["ledger.up.metadata.frames"] == 3
        assert snap["ledger.down.weights.bytes"] == 400
        # no span was open: bytes land in the unattributed bucket
        assert tr.unattributed == {"up/metadata": 150, "down/weights": 400}

    def test_charges_attribute_to_open_span(self):
        tr = obs.Tracer()
        led = obs.MeteredLedger(tr)
        with obs.use_tracer(tr):
            with obs.span("round"):
                with obs.span("select"):
                    led.upload("metadata", 123)
        assert not tr.unattributed
        assert tr.attributed_bytes() == {"up/metadata": 123}
        sel = [s for s in tr.spans if s.name == "select"][0]
        assert sel.bytes == {"up/metadata": 123}


class TestStackedKnowledgeUpload:
    def test_cohort_quantize_runs_in_an_encode_span(self):
        from repro.fl.transport.channel import Channel
        from repro.fl.transport.codecs import Int8Codec
        rng = np.random.default_rng(0)
        b, ck, d = 3, 4, 8
        acts = jnp.asarray(rng.normal(size=(b, ck, d)), jnp.float32)
        ys = jnp.zeros((b, ck), jnp.int32)
        valid = jnp.ones((b, ck), bool)
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            with obs.span("transport", clients=b):
                Channel(obs.MeteredLedger(tr)).upload_knowledge_batched(
                    range(b), acts, ys, valid, Int8Codec())
        enc = [s for s in tr.spans if s.name == "encode"]
        cohort = [s for s in enc if "clients" in s.attrs]
        assert len(cohort) == 1 and cohort[0].attrs == {
            "frame": "knowledge", "clients": b}
        # the cohort's quantize runs before the per-client framing
        per_client = [s for s in enc if "client" in s.attrs]
        assert len(per_client) == b
        assert all(cohort[0].t1 <= s.t0 for s in per_client)


class TestTracer:
    def _tiny_trace(self):
        tr = obs.Tracer(meta={"seed": 0})
        with obs.use_tracer(tr):
            with obs.span("round", round=0):
                with obs.span("select") as sp:
                    sp.set(selected=4)
                obs.event("selection_sketch", client=1, selected=4)
                obs.inc("fault.retransmits", 2)
        return tr

    def test_nested_spans_and_paths(self):
        tr = self._tiny_trace()
        assert [s.name for s in tr.spans] == ["select", "round"]
        recs = tr.to_records()
        assert recs[0]["schema"] == obs.SCHEMA

    def test_jsonl_round_trip(self, tmp_path):
        tr = self._tiny_trace()
        p = tmp_path / "t.jsonl"
        tr.write_jsonl(str(p))
        loaded = obs.load_trace(str(p))
        assert loaded["header"]["meta"] == {"seed": 0}
        assert obs.span_paths(loaded) == {
            "round": {"count": 1, "bytes": 0},
            "round/select": {"count": 1, "bytes": 0}}
        assert loaded["events"][0]["name"] == "selection_sketch"
        assert loaded["metrics"]["snapshot"]["counters"][
            "fault.retransmits"] == 2

    def test_load_rejects_bad_schema_and_bad_json(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"type": "header", "schema": "other/v9"}\n')
        with pytest.raises(obs.TraceError):
            obs.load_trace(str(p))
        p.write_text("not json\n")
        with pytest.raises(obs.TraceError):
            obs.load_trace(str(p))
        p.write_text("")
        with pytest.raises(obs.TraceError):
            obs.load_trace(str(p))

    def test_chrome_export_shapes(self, tmp_path):
        tr = self._tiny_trace()
        p = tmp_path / "t.jsonl"
        tr.write_jsonl(str(p))
        doc = obs.to_chrome(obs.load_trace(str(p)))
        phs = {e["ph"] for e in doc["traceEvents"]}
        assert phs == {"X", "i"}
        assert doc["otherData"]["schema"] == obs.SCHEMA
        assert all(e["ts"] >= 0.0 for e in doc["traceEvents"])

    def test_null_tracer_hooks_are_inert(self):
        # module hooks outside any use_tracer: shared singletons, no state
        sp = obs.span("anything")
        assert sp is obs.NULL_SPAN and not sp.enabled
        assert sp.sync(123) == 123
        obs.event("x")
        obs.inc("c")
        obs.gauge("g", 1.0)
        assert obs.get_tracer() is obs.NULL_TRACER


class TestCLI:
    def _write(self, tmp_path, name, mutate=None):
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            with obs.span("round", round=0):
                obs.event("tick")
        if mutate:
            mutate(tr)
        p = tmp_path / name
        tr.write_jsonl(str(p))
        return str(p)

    def test_summarize_ok(self, tmp_path, capsys):
        p = self._write(tmp_path, "a.jsonl")
        assert obs_cli(["summarize", p]) == 0
        out = capsys.readouterr().out
        assert obs.SCHEMA in out and "round" in out

    def test_diff_identical_is_zero(self, tmp_path):
        a = self._write(tmp_path, "a.jsonl")
        b = self._write(tmp_path, "b.jsonl")
        assert obs_cli(["diff", a, b]) == 0

    def test_diff_structural_change_is_one(self, tmp_path):
        a = self._write(tmp_path, "a.jsonl")

        def extra_span(tr):
            with obs.use_tracer(tr):
                with obs.span("eval"):
                    pass
        b = self._write(tmp_path, "b.jsonl", mutate=extra_span)
        assert obs_cli(["diff", a, b]) == 1

    def test_unreadable_or_malformed_is_two(self, tmp_path):
        a = self._write(tmp_path, "a.jsonl")
        with pytest.raises(SystemExit) as e:
            obs_cli(["diff", a, str(tmp_path / "missing.jsonl")])
        assert e.value.code == 2
        bad = tmp_path / "bad.jsonl"
        bad.write_text("garbage\n")
        with pytest.raises(SystemExit) as e:
            obs_cli(["summarize", str(bad)])
        assert e.value.code == 2
        with pytest.raises(SystemExit) as e:
            obs_cli(["no-such-command"])
        assert e.value.code == 2

    def _compile_run(self, tmp_path, name, fn):
        tr = obs.Tracer()
        with obs.use_tracer(tr):
            with obs.span("round", round=0):
                fn(jnp.ones((3,), jnp.float32)).block_until_ready()
        p = tmp_path / name
        tr.write_jsonl(str(p))
        return str(p), tr

    def test_diff_cold_and_warm_cache_is_zero(self, tmp_path):
        def cold_warm(x):
            return x * 3 + 1

        def compiled(tr):
            return [s for s in tr.spans if s.name == "compile"
                    and s.attrs["fn"] == "jit(cold_warm)"]

        f = jax.jit(cold_warm)
        a, cold = self._compile_run(tmp_path, "cold.jsonl", f)
        b, warm = self._compile_run(tmp_path, "warm.jsonl", f)
        assert compiled(cold) and not compiled(warm)
        assert "compile.jit(cold_warm)" in \
            cold.metrics.snapshot()["counters"]
        assert "compile.jit(cold_warm)" not in \
            warm.metrics.snapshot()["counters"]
        assert obs_cli(["diff", a, b]) == 0

    def test_summarize_shows_compile_seconds_by_parent(self, tmp_path,
                                                       capsys):
        def summarized(x):
            return x - 2

        p, _ = self._compile_run(tmp_path, "c.jsonl", jax.jit(summarized))
        assert obs_cli(["summarize", p]) == 0
        out = capsys.readouterr().out
        line = next(l for l in out.splitlines()
                    if l.startswith("round ") and "compile" not in l
                    and len(l.split()) == 5)
        n_backend, trace_s, lower_s, backend_s = map(float,
                                                     line.split()[1:])
        assert n_backend >= 1 and backend_s > 0 and lower_s > 0
        assert "compile under" in out

    def test_export_chrome_writes_json(self, tmp_path):
        a = self._write(tmp_path, "a.jsonl")
        out = tmp_path / "chrome.json"
        assert obs_cli(["export-chrome", a, str(out)]) == 0
        doc = json.loads(out.read_text())
        assert doc["traceEvents"]


# ------------------------------------------------------- end-to-end runs

@pytest.fixture(scope="module")
def setting():
    cfg = get_wrn_config().reduced()
    model = make_split_wrn(cfg)
    train = SyntheticImageDataset(150, image_size=cfg.image_size,
                                  num_classes=NUM_CLASSES,
                                  modes_per_class=2, noise=0.25, seed=0)
    test = SyntheticImageDataset(60, image_size=cfg.image_size,
                                 num_classes=NUM_CLASSES,
                                 modes_per_class=2, noise=0.25, seed=1)
    clients = partition_k_shards(train, 3, k_classes=2,
                                 samples_per_client=40, seed=0)
    return model, clients, test


@pytest.fixture(scope="module")
def runs(setting):
    """One untraced + one traced run of the same seed (untraced first, so
    it pays compile and the loose overhead guard is conservative)."""
    model, clients, test = setting
    out = {"backend_compiles": {}}
    for name, on in (("off", False), ("on", True)):
        sim = FLSimulation(model, clients, test,
                           FLConfig(**FL_KW, observability=on), seed=0)
        seen = []

        def listen(event, duration, **kwargs):
            if event == BACKEND_COMPILE:
                seen.append(kwargs.get("fun_name"))

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            t0 = monotonic()
            res = sim.run(rounds=ROUNDS)
            out[name] = (sim, res, monotonic() - t0)
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        out["backend_compiles"][name] = seen
    return out


class TestTracedRunFidelity:
    def test_bit_identical_weights_and_ledger(self, runs):
        (s0, r0, _), (s1, r1, _) = runs["off"], runs["on"]
        for a, b in zip(jax.tree.leaves(s0.server.global_params),
                        jax.tree.leaves(s1.server.global_params)):
            assert bool((np.asarray(a) == np.asarray(b)).all())
        assert r0.comm == r1.comm

    def test_result_timing_fields_gate_on_observability(self, runs):
        (_, r0, _), (_, r1, _) = runs["off"], runs["on"]
        assert r0.round_wall_s is None and r0.phase_wall_s is None
        assert len(r1.round_wall_s) == ROUNDS
        assert len(r1.phase_wall_s) == ROUNDS
        for phases in r1.phase_wall_s:
            assert {"broadcast", "cohort", "aggregate"} <= set(phases)
            assert all(v >= 0.0 for v in phases.values())

    def test_every_ledger_byte_attributed(self, runs):
        sim, _, _ = runs["on"]
        att = sim.tracer.attributed_bytes()
        up = sum(v for k, v in att.items() if k.startswith("up/"))
        down = sum(v for k, v in att.items() if k.startswith("down/"))
        assert up == sum(sim.server.ledger.up.values())
        assert down == sum(sim.server.ledger.down.values())
        assert not sim.tracer.unattributed

    def test_span_tree_covers_round_phases(self, runs):
        sim, _, _ = runs["on"]
        names = {s.name for s in sim.tracer.spans}
        assert {"round", "broadcast", "cohort", "client", "select",
                "encode", "decode", "local_update", "aggregate",
                "meta_train", "eval"} <= names

    def test_select_spans_carry_lloyd_iters(self, runs):
        sim, _, _ = runs["on"]
        sels = [s for s in sim.tracer.spans if s.name == "select"]
        assert sels
        for s in sels:
            assert s.attrs.get("lloyd_iters", 0) >= 1
            assert 0.0 <= s.attrs["selected_fraction"] <= 1.0

    def test_overhead_smoke_guard(self, runs):
        # loose: tracing must not blow up the run (the tight <=3% claim
        # is BENCH_obs.json's, measured best-of with warmup); the traced
        # run here even has warm caches, so 1.5x catches only pathology
        (_, _, t_off), (_, _, t_on) = runs["off"], runs["on"]
        assert t_on <= t_off * 1.5 + 1.0

    def test_trace_round_trips_and_diffs_clean(self, runs, tmp_path):
        sim, _, _ = runs["on"]
        p = tmp_path / "run.jsonl"
        sim.tracer.write_jsonl(str(p))
        loaded = obs.load_trace(str(p))
        assert len(loaded["spans"]) == len(sim.tracer.spans)
        assert obs_cli(["diff", str(p), str(p)]) == 0


def _compile_spans(tracer, phase=None):
    return [s for s in tracer.spans if s.name == "compile"
            and (phase is None or s.attrs["phase"] == phase)]


class TestCompileSpans:
    def test_every_meta_train_span_has_compile_children(self, runs):
        sim, _, _ = runs["on"]
        metas = [s for s in sim.tracer.spans if s.name == "meta_train"]
        assert len(metas) == ROUNDS
        parents = {s.parent_id for s in _compile_spans(sim.tracer)}
        for m in metas:
            assert m.span_id in parents
        # the eager scan is compiled again in every round
        fns = [s.attrs["fn"] for s in _compile_spans(sim.tracer, "backend")
               if s.parent_id in {m.span_id for m in metas}]
        assert fns.count("jit(scan)") >= ROUNDS

    def test_compile_spans_lie_inside_their_parent(self, runs):
        sim, _, _ = runs["on"]
        by_id = {s.span_id: s for s in sim.tracer.spans}
        spans = _compile_spans(sim.tracer)
        assert spans
        for s in spans:
            assert s.attrs["phase"] in ("trace", "lower", "backend")
            assert s.t0 <= s.t1
            parent = by_id[s.parent_id]
            assert parent.t0 <= s.t0 and s.t1 <= parent.t1, (s.attrs,
                                                              parent.name)

    def test_backend_spans_match_outside_listener(self, runs):
        sim, _, _ = runs["on"]
        seen = runs["backend_compiles"]["on"]
        spans = _compile_spans(sim.tracer, "backend")
        assert seen and len(spans) == len(seen)
        assert sorted(s.attrs["fn"] for s in spans) == sorted(seen)
        counters = sim.tracer.metrics.snapshot()["counters"]
        assert sum(v for k, v in counters.items()
                   if k.startswith("compile.")) == len(seen)

    def test_observability_off_registers_and_annotates_nothing(self):
        script = f"""
import json
import jax
import jax.profiler
from jax._src import monitoring
opened = []
real = jax.profiler.TraceAnnotation
def counting(name, **kw):
    opened.append(name)
    return real(name, **kw)
jax.profiler.TraceAnnotation = counting
from repro.configs import FLConfig, get_wrn_config
from repro.data import SyntheticImageDataset, partition_k_shards
from repro.fl.simulation import FLSimulation
from repro.models.wrn import make_split_wrn
from repro.obs import compiles
before = (len(monitoring.get_event_listeners()),
          len(monitoring.get_event_time_span_listeners()))
cfg = get_wrn_config().reduced()
train = SyntheticImageDataset(80, image_size=cfg.image_size,
                              num_classes={NUM_CLASSES}, seed=0)
test = SyntheticImageDataset(20, image_size=cfg.image_size,
                             num_classes={NUM_CLASSES}, seed=1)
clients = partition_k_shards(train, 2, k_classes=2, samples_per_client=40,
                             seed=0)
kw = dict({FL_KW!r}, num_clients=2, clients_per_round=2)
FLSimulation(make_split_wrn(cfg), clients, test, FLConfig(**kw),
             seed=0).run(rounds=1)
after = (len(monitoring.get_event_listeners()),
         len(monitoring.get_event_time_span_listeners()))
print(json.dumps({{"before": before, "after": after, "opened": opened,
                  "installed": compiles._LISTENER is not None}}))
"""
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [src, os.environ.get("PYTHONPATH", "")]))
        p = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        got = json.loads(p.stdout.strip().splitlines()[-1])
        assert got["before"] == got["after"]
        assert got["opened"] == [] and not got["installed"]

    def test_profiler_trace_holds_program_span_names(self, setting,
                                                     tmp_path):
        model, clients, test = setting
        sim = FLSimulation(model, clients, test,
                           FLConfig(**FL_KW, observability=True), seed=0)
        with jax.profiler.trace(str(tmp_path)):
            sim.run(rounds=1)
        path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                recursive=True))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for plane in data.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert {"round", "select", "local_update", "aggregate",
                "meta_train"} <= names


class TestSelectionSketch:
    def test_sketch_count_and_shape(self, runs):
        sim, _, _ = runs["on"]
        sk = [e for e in sim.tracer.events
              if e["name"] == "selection_sketch"]
        assert len(sk) == 3 * ROUNDS          # clients x rounds
        for e in sk:
            occ = np.asarray(e["attrs"]["occupancy"])
            assert occ.shape == (NUM_CLASSES, CLUSTERS)
            assert occ.sum() == e["attrs"]["selected"]
            assert 0.0 <= e["attrs"]["selected_fraction"] <= 1.0

    def test_sketch_matches_select_metadata(self, runs, setting):
        """Regression: the trace's occupancy bitmap IS ``select_metadata``'s
        valid mask for that (round, client) — re-derive round 0's keys the
        way the simulation does and recompute client 0's selection."""
        model, clients, _ = setting
        sim, _, _ = runs["on"]
        cfg = FLConfig(**FL_KW, observability=True)
        key = jax.random.PRNGKey(0)
        k_init, key = jax.random.split(key)
        params = model.init(k_init)
        key, k_round, k_sample = jax.random.split(key, 3)
        idx = FLServer(model, params, model.split(params)[1],
                       cfg).sample_clients(len(clients), k_sample)
        keys = jax.random.split(k_round, len(idx))
        i0 = int(idx[0])
        k_sel, _ = jax.random.split(keys[0])
        c = clients[i0]
        acts = model.apply_lower(params, jnp.asarray(c.data.x))
        sel = select_metadata(acts, jnp.asarray(c.data.y), k_sel,
                              num_classes=NUM_CLASSES,
                              clusters_per_class=CLUSTERS,
                              pca_components=cfg.pca_components,
                              kmeans_iters=cfg.kmeans_iters)
        want = np.asarray(sel.valid).astype(int).reshape(NUM_CLASSES,
                                                         CLUSTERS)
        by_id = {s.span_id: s for s in sim.tracer.spans}

        def round_of(ev):
            sp = by_id[ev["parent"]]
            while "round" not in sp.attrs:
                sp = by_id[sp.parent_id]
            return sp.attrs["round"]

        ev = [e for e in sim.tracer.events
              if e["name"] == "selection_sketch" and round_of(e) == 0
              and e["attrs"]["client"] == i0]
        assert len(ev) == 1
        assert (np.asarray(ev[0]["attrs"]["occupancy"]) == want).all()


class TestChaosTrace:
    def test_trace_counters_match_channel_totals(self, setting):
        model, clients, test = setting
        plan = FaultPlan(bitflip_rate=0.4, truncate_rate=0.2,
                         duplicate_rate=0.2, max_retries=2)
        sim = FLSimulation(model, clients, test,
                           FLConfig(**FL_KW, observability=True,
                                    transport_checksum=True),
                           seed=0, fault_plan=plan, fault_seed=3)
        res = sim.run(rounds=ROUNDS)
        tr = sim.tracer
        counters = tr.metrics.snapshot()["counters"]
        ch = sim.channel
        assert ch.total_injected_corruptions > 0   # the plan actually bit
        assert counters["fault.injected_corruptions"] == \
            ch.total_injected_corruptions
        detected = sum(1 for e in tr.events
                       if e["name"] == "fault.corrupt_detected")
        assert detected == sum(res.corruptions_detected)
        assert counters.get("fault.retransmits", 0) == sum(res.retransmits)
        # CRC on: every injected corruption is detected or lost, never
        # silently consumed — mirrored in the trace
        assert counters.get("fault.silent_corruption", 0) == 0
        assert not tr.unattributed
