#!/usr/bin/env python3
"""The chip benchmark: synchronous split-FL rounds on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run finds a TPU or fails, turns on the persistent compile cache, has the
configuration's model family (``bench/families/<family>.py``, see
``bench/spec.py``) build the cell's traffic, weights and ``FLSimulation``
from ``--seed``, warms up with one round, and then drives
``FLSimulation._run_round`` (broadcast -> cohort -> ``FLServer.aggregate``,
which yields ``W_G(t)``) round after round until ``--seconds`` have passed;
every round started in the window counts, whole. Evaluation on a test set
is never run. With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` the program's spans are on, the calls into each layer
carry profiler annotations, the window is traced, and it reports the
per-layer metrics.

After the window the program's state is freed and the family's plain
reference follows the first rounds from the same weights and keys;
``correct`` holds when every number compared is within its limit
(``limits`` in the cell's file). The last line of standard output is one
JSON object; the numbers compared, each with its limit, are the last lines
of standard error and the last key of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import ExitStack, contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for _p in (str(ROOT / "src"), str(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from spec import (SpecError, load_cell, load_family, load_peaks,  # noqa: E402
                  load_reader)

NEVER = 1 << 62            # an eval interval no round reaches
WINDOW_MARK = "bench.window"
TRACE_ROUNDS = 2           # rounds a --trace 1 window holds at most


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def clock() -> float:
    return time.perf_counter()


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"JAX reports platform {info['platform']!r}, not tpu")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{info['count']}")
    return info


def enable_cache() -> str:
    import jax
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles (persistent-cache reads included), from
    ``jax.monitoring``, counted while ``on``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.on = False
        self.count = 0
        self.seconds = 0.0          # compile seconds, counted or not
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kwargs):
        if event == self.event:
            self.seconds += duration
            if self.on:
                self.count += 1


@contextmanager
def annotations(table):
    """Wrap each ``module:attr`` (or ``module:Class.method``) of ``table``
    in a ``jax.profiler.TraceAnnotation`` of its name, and restore it."""
    import jax
    undo = []
    try:
        for entry in table:
            mod_name, attr = entry["target"].split(":")
            owner = importlib.import_module(mod_name)
            *path, leaf = attr.split(".")
            for part in path:
                owner = getattr(owner, part)
            orig = owner.__dict__[leaf] if isinstance(owner, type) \
                else getattr(owner, leaf)

            def wrapped(*a, __orig=orig, __name=entry["name"], **kw):
                with jax.profiler.TraceAnnotation(__name):
                    return __orig(*a, **kw)

            setattr(owner, leaf, wrapped)
            undo.append((owner, leaf, orig))
        yield
    finally:
        for owner, leaf, orig in reversed(undo):
            setattr(owner, leaf, orig)


def drive_round(sim, t: int, res) -> None:
    """One whole round of the program, ended on the device."""
    import jax
    from repro import obs
    with obs.use_tracer(sim.tracer):
        with obs.span("round", round=t) as rsp:
            sim._run_round(t, NEVER, NEVER, False, res, rsp)
    jax.block_until_ready(sim.server.global_params)


def up_bytes(ledger) -> int:
    return int(ledger.up.get("metadata", 0) + ledger.up.get("weights", 0))


def span_records(tracer, t0: float, t1: float):
    return [sp.to_record() for sp in getattr(tracer, "spans", [])
            if sp.t0 >= t0 and sp.t1 <= t1]


def judge(numbers: dict, limits: dict):
    """-> (correct, [(name, value, limit)]). Every limit must be read, and
    a number that is not finite is over any limit."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        good = math.isfinite(value) and value <= limit
        ok &= good
        rows.append((name, value, limit))
    return ok, rows


def measure(cell, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, root: Path = ROOT,
            cache: bool = True) -> dict:
    """A whole run of ``cell``; returns the result line as a dict.
    ``require_tpu`` and ``cache`` are off only in the tests on the CPU."""
    device = device_info(cell.chips, require_tpu)
    import jax
    if cache:
        enable_cache()
    # the program runs at the precision the configuration states
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul"])
    counter = CompileCounter()

    import build
    from repro.fl.simulation import SimulationResult

    config, traffic, workload = cell.config, cell.traffic, cell.workload
    family = load_family(cell.family, root)
    fl = build.fl_settings(config, traffic)
    arch = family.arch(config)
    t_data = clock()
    clients = family.make_traffic(traffic, arch, seed)
    t_data = clock() - t_data
    w0 = family.make_weights(arch, seed)
    w0_host = family.to_host(w0)
    check_rounds = int(workload["check_rounds"])
    capture = build.Captured(check_rounds)
    sim, cfg = family.make_simulation(config, traffic, clients, w0, seed,
                                      trace, capture)
    res = SimulationResult()
    t_warm, c_warm = clock(), counter.seconds
    drive_round(sim, 0, res)                      # warm-up: one round
    t_warm, c_warm = clock() - t_warm, counter.seconds - c_warm
    ledger = sim.server.ledger
    table = (json.loads((BENCH_DIR / "annotations.json").read_text())
             + list(getattr(family, "ANNOTATIONS", ())))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else ""

    with ExitStack() as stack:
        if trace:
            stack.enter_context(annotations(table))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        bytes0, t = up_bytes(ledger), 1
        setup_s = clock() - T_START
        c_setup = counter.seconds
        counter.on = True
        w_start = clock()
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            while (clock() - w_start < seconds
                   and not (trace and t > TRACE_ROUNDS)):
                with jax.profiler.StepTraceAnnotation("round", step_num=t):
                    drive_round(sim, t, res)
                t += 1
        w_end = clock()
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
    rounds = t - 1
    updates = rounds * min(fl["clients_per_round"], fl["num_clients"])
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in jax.devices()[:cell.chips])

    ctx = {
        "window_s": w_end - w_start, "rounds": rounds, "updates": updates,
        "setup_s": setup_s, "uplink_bytes": up_bytes(ledger) - bytes0,
        "compiles": counter.count, "config": config, "traffic": traffic,
        "fl": {**fl, **family.fl_extra(traffic)},
        "peaks": load_peaks(device["kind"], root) if require_tpu else None,
        "spans": span_records(sim.tracer, w_start, w_end) if trace else [],
        "trace": None,
    }
    ctx["shapes"] = family.shapes(config, traffic, ctx["fl"])
    ctx["flops"] = family.round_flops(config, traffic, ctx["fl"])
    line = {"device": dict(device, memory_peak_bytes=mem),
            "setup_parts": {"data_s": t_data, "warmup_round_s": t_warm,
                            "compile_s_in_warmup": c_warm,
                            "compile_s_in_setup": c_setup,
                            "compile_s_in_window": counter.seconds - c_setup}}
    if trace:
        import xplane as T
        path = T.find_xplane(trace_dir)
        names = {e["name"] for e in table} | {WINDOW_MARK, "round"}
        if path is not None:
            ctx["trace"] = T.read_xplane(path, WINDOW_MARK, names)
            line["device"]["busy_s"] = ctx["trace"]["busy_s"]
            line["device"]["window_s"] = ctx["trace"]["window_s"]
            line["breakdown"] = T.breakdown(ctx["trace"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = cell.per_layer if trace else cell.end_to_end
    line["metrics"] = {}
    for m in metrics:
        value = load_reader(m.name, root)(ctx)
        if value is not None:
            line["metrics"][m.name] = {"value": value, "unit": m.unit}

    dropped = int(sum(res.drops[1:]))
    # the program's state goes before the reference runs
    prog = family.captured_rounds(capture)
    del sim, capture, ctx, res
    gc.collect()
    t_ref = clock()
    numbers = family.follow(arch, fl, clients, w0_host, seed, prog)
    correct, rows = judge(numbers, workload["limits"])
    if numbers.get("rounds_checked", 0) < min(check_rounds, rounds + 1):
        correct = False
    t_ref = clock() - t_ref
    line["setup_parts"]["reference_s"] = t_ref
    line = {"correct": bool(correct), "attempted": updates,
            "failed": dropped, **line,
            "checked": {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}}
    line["checked"]["rounds_checked"] = {
        "value": numbers.get("rounds_checked", 0),
        "limit": min(check_rounds, rounds + 1)}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        line = measure(cell, args.seed, args.seconds, bool(args.trace))
    except (SpecError, NoChip) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, row in line["checked"].items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
