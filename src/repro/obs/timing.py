"""repro.obs.timing — the one wall-clock for the whole repo.

Every benchmark and runtime phase measurement goes through ``monotonic``
(a monotonic high-resolution counter; ``time.time()`` is wall-clock and
can step backwards under NTP) or ``timeit`` (warmup-aware, device-sync
aware). flcheck rule OBS001 enforces that no other module reads
``time.time``/``perf_counter`` directly.

Import-safe without jax: the analysis CI job runs ``python -m
repro.analysis`` with no jax installed, and that path imports this
module. jax is only touched lazily inside ``sync``/``timeit``.
"""
from __future__ import annotations

import time as _time
from typing import Any, Callable, NamedTuple

# flcheck: disable=OBS001 (this module IS the sanctioned clock)
monotonic: Callable[[], float] = _time.perf_counter


def wall() -> float:
    """Seconds since the epoch (``time.time()``): a timestamp for records,
    never a duration — it can step under NTP."""
    return _time.time()


def from_wall(t: float) -> float:
    """A ``time.time()`` stamp (as ``jax.monitoring`` time-span events
    carry) on the ``monotonic`` clock: the stamp's age on the wall clock,
    taken back from ``monotonic()`` now. Meant for stamps a moment old,
    over which the two clocks cannot drift apart."""
    return monotonic() - (wall() - t)


def sync(x: Any) -> Any:
    """Block until device work backing ``x`` is done (identity without
    jax, on None, or on abstract tracers during jit tracing)."""
    if x is None:
        return x
    try:
        import jax
    except ImportError:  # pragma: no cover - analysis-only environment
        return x
    if any(isinstance(l, jax.core.Tracer) for l in jax.tree_util.tree_leaves(x)):
        return x
    return jax.block_until_ready(x)


class Timing(NamedTuple):
    """Result of ``timeit``: seconds per call + the (synced) last output."""
    seconds: float
    out: Any


def timeit(fn: Callable[..., Any], *args: Any, iters: int = 5,
           warmup: int = 1, reduce: str = "mean", **kwargs: Any) -> Timing:
    """Warmup-aware timer: run ``fn(*args, **kwargs)`` ``warmup`` times
    (compile/caches), then time ``iters`` calls, blocking on the output
    each iteration so async device dispatch is not under-counted.

    ``reduce`` is ``"mean"`` (default, matches the kernel benches) or
    ``"min"`` (best-of, noise-robust, matches the selection bench).
    """
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    out = None
    for _ in range(warmup):
        out = sync(fn(*args, **kwargs))
    samples = []
    for _ in range(iters):
        t0 = monotonic()
        out = sync(fn(*args, **kwargs))
        samples.append(monotonic() - t0)
    if reduce == "mean":
        return Timing(sum(samples) / len(samples), out)
    if reduce == "min":
        return Timing(min(samples), out)
    raise ValueError(f"unknown reduce {reduce!r} (want 'mean' or 'min')")
