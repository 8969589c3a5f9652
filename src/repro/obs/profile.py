"""repro.obs.profile — ``profiled_jit`` and the offline cost record.

``profiled_jit`` wraps ``jax.jit`` for the runtime's compiled hot paths
(the §3.1 selection pipeline, the Pallas kernel entries, the stacked
LocalUpdate). A call is the one underlying jitted callable, traced or
not; what a call compiles shows in a traced run as ``compile`` spans,
taken from JAX's own compile events (``repro.obs.compiles``).

Offline, ``ProfiledFunction.cost`` derives a :class:`CostRecord` from the
compiled module's HLO text — ``launch/hlo_analysis.py`` is the repo's ONE
FLOP/byte deriver and this module is its façade — for the dry-run and
benchmark rooflines (``roofline``, ``peak_table``).

Import-safe without jax: jax, ``launch.hlo_analysis`` and ``launch.mesh``
are only imported lazily inside calls (the flcheck CI job imports
``repro.obs`` with no jax installed).
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple


# --------------------------------------------------------------------------
# the one cost record (façade over launch/hlo_analysis.parse_hlo)
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class CostRecord:
    """Per-compiled-function cost, derived from post-SPMD HLO text.

    ``flops``/``hbm_bytes`` are while-loop-trip-expanded where XLA records
    ``known_trip_count`` (fori_loop); a *dynamic* while (the early-exit
    Lloyd loop) counts its body once and bumps ``unknown_trip_loops`` —
    the record is then a lower bound, flagged, never a guess."""
    flops: float = 0.0
    hbm_bytes: float = 0.0
    transcendentals: float = 0.0
    collective_bytes: float = 0.0
    unknown_trip_loops: int = 0

    def as_dict(self) -> Dict[str, float]:
        """Plain-dict form for JSON reports."""
        return {"flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "transcendentals": self.transcendentals,
                "collective_bytes": self.collective_bytes,
                "unknown_trip_loops": self.unknown_trip_loops}


def record_from_hlo(hc: Any) -> CostRecord:
    """``launch/hlo_analysis.HloCost`` -> :class:`CostRecord` — the one
    place the parser's fields are mapped into the record the rest of the
    repo consumes (dry-run keeps the parsed object for per-kind collective
    detail but takes its totals from here)."""
    return CostRecord(flops=hc.flops, hbm_bytes=hc.bytes,
                      transcendentals=hc.transcendentals,
                      collective_bytes=hc.collective_total,
                      unknown_trip_loops=hc.unknown_trips)


def cost_from_hlo_text(text: str) -> CostRecord:
    """The repo's single FLOP deriver: ``launch/hlo_analysis.parse_hlo``
    re-exposed as a :class:`CostRecord` (dry-run, roofline tables and
    ``ProfiledFunction.cost`` all route through here)."""
    from repro.launch.hlo_analysis import parse_hlo
    return record_from_hlo(parse_hlo(text))


def cost_from_compiled(compiled: Any) -> CostRecord:
    """Cost of a ``jax`` AOT ``Compiled`` object (``jit.lower().compile()``)."""
    return cost_from_hlo_text(compiled.as_text())


def record_from_dryrun(rec: Dict[str, Any]) -> CostRecord:
    """Rebuild the cost record from a saved dry-run JSON (``launch/dryrun``
    output) so ``benchmarks/roofline_report.py`` renders from the same
    record type ``ProfiledFunction.cost`` derives."""
    cost = rec.get("cost", {})
    coll = rec.get("collectives", {})
    return CostRecord(
        flops=float(cost.get("flops_expanded", cost.get("flops", 0.0))),
        hbm_bytes=float(cost.get("bytes_expanded",
                                 cost.get("bytes accessed", 0.0))),
        transcendentals=float(cost.get("transcendentals", 0.0)),
        collective_bytes=float(coll.get("total_bytes", 0.0)),
        unknown_trip_loops=int(coll.get("unknown_trip_counts", 0)))


# --------------------------------------------------------------------------
# per-device peak table
# --------------------------------------------------------------------------
# Host-CPU peaks are order-of-magnitude estimates (a couple of AVX cores),
# kept for the CPU test runs; the TPU entry is the v5e datasheet via
# launch/mesh.py (single source), under the kind the chip reports.
_CPU_PEAKS = {"peak_flops_bf16": 2.0e11, "peak_flops_f32": 1.0e11,
              "hbm_bw": 2.0e10, "ici_bw": 0.0}


def peak_table(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and memory bandwidth for a device, keyed by its
    ``device_kind`` (``jax.devices()[0].device_kind``: ``"cpu"`` or the
    v5e's ``launch.mesh.V5E_DEVICE_KIND``). The LM dry-run rooflines use
    bf16; ``peak_flops_f32`` is read by ``benchmarks/selection_bench.py``
    alone. A kind with no entry raises: a guessed peak would make every
    utilization figure wrong."""
    from repro.launch import mesh
    if device_kind == "cpu":
        return dict(_CPU_PEAKS)
    if device_kind == mesh.V5E_DEVICE_KIND:
        return {"peak_flops_bf16": mesh.PEAK_FLOPS_BF16,
                "peak_flops_f32": mesh.PEAK_FLOPS_BF16 / 2,
                "hbm_bw": mesh.HBM_BW, "ici_bw": mesh.ICI_BW}
    raise ValueError(f"no peak table for device kind {device_kind!r}")


def roofline(cost: CostRecord, peaks: Dict[str, float],
             dtype: str = "f32") -> Dict[str, Any]:
    """The three roofline terms + binding resource for one cost record —
    the single roofline calculator (dry-run reports and the selection
    bench both call this)."""
    peak = peaks[f"peak_flops_{dtype}"]
    compute_s = cost.flops / peak if peak else 0.0
    memory_s = cost.hbm_bytes / peaks["hbm_bw"] if peaks["hbm_bw"] else 0.0
    ici = peaks.get("ici_bw", 0.0)
    collective_s = cost.collective_bytes / ici if ici else 0.0
    bound = max((("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)), key=lambda kv: kv[1])[0]
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "bound": bound}


# --------------------------------------------------------------------------
# profiled_jit
# --------------------------------------------------------------------------
def _abstract(leaf: Any) -> str:
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return f"{leaf.dtype}{tuple(leaf.shape)}"
    return f"{type(leaf).__name__}:{leaf!r}"


class ProfiledFunction:
    """``jax.jit`` plus the offline cost layer.  Execution always goes
    through the one underlying jitted callable (so traced and untraced
    runs share jax's dispatch cache and stay bit-identical)."""

    def __init__(self, fn: Callable, *, name: Optional[str] = None,
                 static_argnames: Tuple[str, ...] = ()) -> None:
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "fn")
        self.static_argnames = tuple(static_argnames)
        self.__doc__ = getattr(fn, "__doc__", None)
        self.__name__ = self.name
        self._jitted: Any = None
        self._pysig: Any = None
        self._costs: Dict[str, Optional[CostRecord]] = {}

    # -- lazy jax plumbing -------------------------------------------
    def _jit(self) -> Any:
        if self._jitted is None:
            import jax
            self._jitted = jax.jit(self.fn,
                                   static_argnames=self.static_argnames)
        return self._jitted

    def signature_key(self, args: tuple, kwargs: dict) -> str:
        """Abstract call signature: (shape, dtype) per array leaf, repr
        for statics — mirrors jax's jit cache key closely enough to key
        the cost records."""
        import jax
        if self._pysig is None:
            try:
                self._pysig = inspect.signature(self.fn)
            except (TypeError, ValueError):  # pragma: no cover
                self._pysig = False
        dyn, static = (args, dict(kwargs)), {}
        if self._pysig:
            try:
                bound = self._pysig.bind(*args, **kwargs)
                bound.apply_defaults()
                static = {k: v for k, v in bound.arguments.items()
                          if k in self.static_argnames}
                dyn = {k: v for k, v in bound.arguments.items()
                       if k not in self.static_argnames}
            except TypeError:
                pass
        leaves, treedef = jax.tree_util.tree_flatten(dyn)
        parts = [_abstract(l) for l in leaves]
        parts.append(str(treedef))
        parts.append(repr(sorted((k, repr(v)) for k, v in static.items())))
        return "|".join(parts)

    def _derive_cost(self, sig: str, args: tuple,
                     kwargs: dict) -> Optional[CostRecord]:
        cost = self._costs.get(sig)
        if cost is not None or sig in self._costs:
            return cost
        try:
            compiled = self._jit().lower(*args, **kwargs).compile()
            cost = cost_from_compiled(compiled)
        except Exception:  # cost is telemetry; never fail the call for it
            cost = None
        self._costs[sig] = cost
        return cost

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        """``jax.jit(fn).lower(...)`` of the one underlying jitted callable:
        the program a call with these arguments (arrays or
        ``jax.ShapeDtypeStruct``s) compiles."""
        return self._jit().lower(*args, **kwargs)

    def cost(self, *args: Any, **kwargs: Any) -> Optional[CostRecord]:
        """The :class:`CostRecord` this call signature would compile to
        (derives + caches on first use; no tracer required — benchmarks
        use this for their measured-FLOPs rows)."""
        return self._derive_cost(self.signature_key(args, kwargs),
                                 args, kwargs)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        return self._jit()(*args, **kwargs)


def profiled_jit(fn: Optional[Callable] = None, *,
                 name: Optional[str] = None,
                 static_argnames: Tuple[str, ...] = ()) -> Any:
    """Decorator/factory: ``jax.jit`` with the offline cost layer.

    Use exactly like ``functools.partial(jax.jit, static_argnames=...)``::

        @profiled_jit(static_argnames=("k",))
        def kmeans(x, k, ...): ...

    or inline: ``prof = profiled_jit(kernel_fn, name="lloyd",
    static_argnames=("block_n", "interpret"))``."""
    if fn is None:
        return lambda f: ProfiledFunction(f, name=name,
                                          static_argnames=static_argnames)
    return ProfiledFunction(fn, name=name, static_argnames=static_argnames)
