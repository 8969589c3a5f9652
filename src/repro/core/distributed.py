"""Pod-scale round engine — the ``repro.core.distributed`` runtime that
``rounds.py``/``fl/client.py`` promise.

The simulator (``repro.core.rounds``) runs Algorithm 1 at per-client
granularity; this module runs the same math with the whole cohort STACKED:

  select_cohort        Extract&Selection (§3.1) over a stacked cohort,
                       (a) sharded over the mesh's ``data`` axis with
                       ``shard_map`` — a pod of devices selects for
                       device-count x the clients per call — and
                       (b) streamed in client CHUNKS with each chunk's
                       activations gathered down to the selected metadata
                       before the next chunk runs (``gather=True``), so a
                       mega-cohort's activation memory is one chunk's, not
                       the cohort's (the old ``MAX_BATCHED_ELEMENTS``
                       fall-back-to-sequential cliff is gone; the input
                       stack itself — the clients' raw data — is the
                       irreducible footprint of the stacked engine).
  local_update_cohort  LocalUpdate (§3.2) as ONE compiled ``local_update``
                       over the stacked cohort (lax.map over the client
                       axis, shard_map across devices) — the last
                       per-client Python loop in the round is gone.
  cohort_round         both of the above plus the ledger accounting, i.e.
                       the whole client side of a round.
  run_round_distributed  Algorithm 1 end to end on the stacked cohort.

Every client's selection and local update are independent, so chunking and
sharding are pure schedules: results are bit-identical to the sequential
per-client loop (asserted by tests/test_distributed.py and
tests/test_core_fl.py).
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro import obs
from repro.configs.base import FLConfig
from repro.core.selection import (Selection, select_metadata,
                                  select_metadata_batched)
from repro.core.split import SplitModel
from repro.data.partition import ClientData
from repro.fl.comms import CommLedger
from repro.obs.profile import profiled_jit

PyTree = Any


# --------------------------------------------------------------------------
# cohort stacking
# --------------------------------------------------------------------------
def cohort_is_stackable(clients: List[ClientData]) -> bool:
    """A cohort stacks when every client's data shapes agree (the ragged
    case stays on the sequential per-client path)."""
    return len({(c.data.x.shape, c.data.y.shape) for c in clients}) == 1


def cohort_arrays(clients: List[ClientData]
                  ) -> Optional[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Stack the cohort's data -> (xs (B, N, ...), ys (B, N)), or None when
    the cohort is ragged."""
    if not cohort_is_stackable(clients):
        return None
    xs = jnp.stack([jnp.asarray(c.data.x) for c in clients])
    ys = jnp.stack([jnp.asarray(c.data.y) for c in clients])
    return xs, ys


def selection_mesh(num_devices: int = 0) -> Mesh:
    """A 1-D ``data`` mesh over the host's devices for sharded selection
    (the production pod meshes live in ``launch/mesh.py``; selection only
    needs the client axis)."""
    n = num_devices or len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def data_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)


def _pad_clients(arrays, ndev: int):
    """Pad every array's leading client axis to a multiple of ``ndev`` with
    copies of client 0 (their outputs are discarded — selections/updates are
    client-independent). Returns (padded arrays, unpad fn)."""
    b = arrays[0].shape[0]
    pad = (-b) % ndev
    if not pad:
        return arrays, lambda tree: tree
    padded = tuple(
        None if a is None else
        jnp.concatenate([a, jnp.repeat(a[:1], pad, axis=0)], axis=0)
        for a in arrays)
    return padded, lambda tree: jax.tree.map(lambda a: a[:b], tree)


def cohort_inputs_fit(clients: List[ClientData]) -> bool:
    """Whether the cohort's RAW INPUT stack fits the stacked engine's
    memory budget. Chunking bounds the per-chunk activation footprint, but
    the input stack itself is the engine's irreducible footprint — past
    this the sequential per-client loop (one client's data at a time) is
    the escape hatch, exactly as before chunking existed. The budget is
    deliberately NOT scaled by the mesh width: ``cohort_arrays`` commits
    the stack to the default device before shard_map reshards it, so one
    device must hold it (sharded-at-stack-time device_put is a ROADMAP
    item)."""
    from repro.core.rounds import MAX_BATCHED_ELEMENTS
    elements = len(clients) * int(np.prod(clients[0].data.x.shape))
    return elements <= MAX_BATCHED_ELEMENTS


def auto_chunk_size(model: SplitModel, params: PyTree, x_shape, x_dtype,
                    num_clients: int, data_axis: int = 1) -> int:
    """Streaming chunk size for a cohort: 0 (one stack) while the stacked
    inputs + activations fit ``rounds.MAX_BATCHED_ELEMENTS``, else the
    largest client count whose stack does. ``data_axis`` scales the budget
    for a sharded chunk (each device holds chunk/data_axis clients)."""
    from repro.core.rounds import MAX_BATCHED_ELEMENTS
    act_shape = jax.eval_shape(
        lambda x: model.apply_lower(params, x),
        jax.ShapeDtypeStruct(x_shape, x_dtype)).shape
    per_client = int(np.prod(x_shape)) + int(np.prod(act_shape))
    budget = MAX_BATCHED_ELEMENTS * max(data_axis, 1)
    if num_clients * per_client <= budget:
        return 0
    return max(1, budget // per_client)


# --------------------------------------------------------------------------
# Extract & Selection over a stacked cohort (§3.1)
# --------------------------------------------------------------------------
def _select_stack(model: SplitModel, params: PyTree, xs: jnp.ndarray,
                  ys: jnp.ndarray, sel_keys: jax.Array, cfg: FLConfig,
                  num_classes: int):
    """The vmapped lower forward + §3.1 pipeline on one stacked chunk."""
    acts = jax.vmap(lambda x: model.apply_lower(params, x))(xs)
    sels = select_metadata_batched(
        acts, ys, sel_keys, num_classes=num_classes,
        clusters_per_class=cfg.clusters_per_class,
        pca_components=cfg.pca_components, kmeans_iters=cfg.kmeans_iters,
        use_pallas=cfg.use_pallas_selection, pca_solver=cfg.pca_solver)
    return acts, sels


def _select_stack_sharded(model: SplitModel, params: PyTree, xs: jnp.ndarray,
                          ys: jnp.ndarray, sel_keys: jax.Array, cfg: FLConfig,
                          num_classes: int, mesh: Mesh):
    """shard_map over the mesh's ``data`` axis: each device runs the §3.1
    pipeline on its local slice of the client axis (no collectives —
    selections are client-independent). Within a shard the clients are
    ``lax.map``-ed, not vmapped: re-batching the pipeline inside the SPMD
    module re-fuses the PCA matmuls and perturbs the eigh just enough to
    flip near-degenerate selections (~1e-4 feature drift), while the
    lax.map body compiles to the same per-client HLO as the sequential
    simulator — bit-identical selections. Cross-client parallelism is the
    device axis itself (size the cohort ~ the axis for full utilization).
    The cohort is padded with copies of client 0 up to a multiple of the
    axis size; padded outputs are sliced away."""
    ndev = data_axis_size(mesh)
    (xs, ys, sel_keys), unpad = _pad_clients((xs, ys, sel_keys), ndev)

    def shard_fn(p, x, y, k):
        # forward under vmap (bit-stable for the conv/matmul forward, as
        # the batched simulator path established) ...
        acts = jax.vmap(lambda xx: model.apply_lower(p, xx))(x)

        def one(args):
            a, yy, kk = args
            return select_metadata(
                a, yy, kk, num_classes=num_classes,
                clusters_per_class=cfg.clusters_per_class,
                pca_components=cfg.pca_components,
                kmeans_iters=cfg.kmeans_iters,
                use_pallas=cfg.use_pallas_selection,
                pca_solver=cfg.pca_solver)

        # ... selection under lax.map (bit-stable for the PCA eigh)
        return acts, jax.lax.map(one, (acts, y, k))

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(), P("data"), P("data"), P("data")),
                       out_specs=P("data"), check_vma=False)
    return unpad(fn(params, xs, ys, sel_keys))


def select_cohort(model: SplitModel, params: PyTree, xs: jnp.ndarray,
                  ys: jnp.ndarray, keys: jax.Array, cfg: FLConfig,
                  num_classes: int, *, chunk_size: int = 0,
                  mesh: Optional[Mesh] = None, gather: bool = False):
    """Batched Extract&Selection for a stacked cohort.

    keys are the per-client ROUND keys (each client's selection key is
    derived exactly as ``rounds.client_round`` derives its own, so stacked
    and sequential rounds select identically). ``chunk_size > 0`` streams
    the cohort through the pipeline ``chunk_size`` clients at a time (on a
    ``mesh`` with a ``data`` axis wider than 1, each chunk's client axis is
    additionally sharded across devices; the chunk is rounded up to a
    multiple of the axis so full chunks carry no pad clients — a ragged
    FINAL chunk still pads up to the axis).

    gather=False returns (acts (B, N, ...), Selection) — the full cohort's
    activation stack, so only the per-chunk PIPELINE intermediates are
    bounded. gather=True returns the per-client metadata
    (sel_acts (B, CK, ...), sel_ys (B, CK), valid (B, CK),
    lloyd_iters (B,)) with each
    chunk's activations/features gathered down and DROPPED before the next
    chunk runs — the mega-cohort mode, where device memory holds the input
    stack plus one chunk's activations, never the cohort's.
    """
    b = xs.shape[0]
    sel_keys = jax.vmap(lambda k: jax.random.split(k)[0])(jnp.asarray(keys))
    use_mesh = mesh if data_axis_size(mesh) > 1 else None
    if use_mesh is not None and 0 < chunk_size < b:
        ndev = data_axis_size(use_mesh)
        chunk_size = -(-chunk_size // ndev) * ndev

    take0 = jax.vmap(lambda a, i: jnp.take(a, i, axis=0))

    def one(lo, hi):
        if use_mesh is not None:
            acts, sels = _select_stack_sharded(
                model, params, xs[lo:hi], ys[lo:hi], sel_keys[lo:hi], cfg,
                num_classes, use_mesh)
        else:
            acts, sels = _select_stack(model, params, xs[lo:hi], ys[lo:hi],
                                       sel_keys[lo:hi], cfg, num_classes)
        if gather:
            return (take0(acts, sels.indices), take0(ys[lo:hi], sels.indices),
                    sels.valid, sels.lloyd_iters)
        return acts, sels

    if chunk_size <= 0 or chunk_size >= b:
        return one(0, b)
    parts = [one(lo, min(lo + chunk_size, b))
             for lo in range(0, b, chunk_size)]
    if gather:
        return tuple(jnp.concatenate(fs, axis=0) for fs in zip(*parts))
    acts = jnp.concatenate([a for a, _ in parts], axis=0)
    sel = Selection(*(jnp.concatenate(fs, axis=0)
                      for fs in zip(*(s for _, s in parts))))
    return acts, sel


def select_metadata_sharded(acts: jnp.ndarray, labels: Optional[jnp.ndarray],
                            keys: jax.Array, mesh: Mesh,
                            **kwargs) -> Selection:
    """shard_map of the §3.1 pipeline over PRECOMPUTED activation stacks:
    the client axis of (B, N, ...) acts splits over the mesh's ``data``
    axis, each device lax.maps its shard (bit-identical to the sequential
    loop — see ``_select_stack_sharded``). The round engine fuses the lower
    forward in; this is the acts-level entry the selection benchmark
    shards. ``kwargs`` are ``select_metadata``'s static knobs."""
    ndev = data_axis_size(mesh)
    (acts, keys, labels), unpad = _pad_clients(
        (acts, jnp.asarray(keys), labels), ndev)

    def one(args):
        a, y, k = args
        return select_metadata(a, y, k, **kwargs)

    if labels is None:
        fn = jax.shard_map(
            lambda a, k: jax.lax.map(lambda t: select_metadata(
                t[0], None, t[1], **kwargs), (a, k)),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False)
        sels = fn(acts, keys)
    else:
        fn = jax.shard_map(lambda a, y, k: jax.lax.map(one, (a, y, k)),
                           mesh=mesh,
                           in_specs=(P("data"), P("data"), P("data")),
                           out_specs=P("data"), check_vma=False)
        sels = fn(acts, labels, keys)
    return unpad(sels)


# --------------------------------------------------------------------------
# LocalUpdate over a stacked cohort (§3.2)
# --------------------------------------------------------------------------
def _client_update(model: SplitModel, cfg: FLConfig, params: PyTree):
    """The per-client body the stacked LocalUpdate maps over (x, y, round
    key): the round key's second half shuffles, exactly as
    ``rounds.client_round`` derives it. Returns (new params, mean loss)."""
    # lazy: rounds imports this module
    from repro.core.rounds import client_local_update

    def one(args):
        x, y, key = args
        new_p, losses = client_local_update(model, cfg, params, x, y,
                                            jax.random.split(key)[1])
        return new_p, losses.mean()

    return one


@profiled_jit(name="local_update_stack", static_argnames=("model", "cfg"))
def _local_update_stack(model: SplitModel, cfg: FLConfig, params: PyTree,
                        xs: jnp.ndarray, ys: jnp.ndarray, keys: jax.Array):
    """The single-host stacked LocalUpdate as one compiled function
    (``model``/``cfg`` are frozen dataclasses, so they key the jit cache
    as statics — a per-round cache miss on the cohort hot path then
    shows as a ``compile`` span in every traced round)."""
    return jax.lax.map(_client_update(model, cfg, params), (xs, ys, keys))


def local_update_cohort(model: SplitModel, params: PyTree, xs: jnp.ndarray,
                        ys: jnp.ndarray, keys: jax.Array, cfg: FLConfig,
                        mesh: Optional[Mesh] = None):
    """LocalUpdate over the stacked cohort in ONE compiled computation:
    every client starts from the same global params, shuffles with its own
    key (same derivation as ``rounds.client_round``), and runs the same SGD
    scan. Returns (stacked client params with leading B axis, (B,) losses).

    The client axis is ``lax.map``-ed, not vmapped: vmap re-batches the
    convolution *gradients* into different reduction orders (~1e-7 drift vs
    the sequential loop), while lax.map keeps each client's HLO identical —
    bit-identical results with the Python-loop dispatch overhead still gone.
    Cross-client parallelism comes from ``mesh`` instead: shard_map splits
    the client axis over the ``data`` axis and each device maps its shard."""
    keys = jnp.asarray(keys)

    if data_axis_size(mesh) > 1:
        one = _client_update(model, cfg, params)
        (xs, ys, keys), unpad = _pad_clients((xs, ys, keys),
                                             data_axis_size(mesh))
        fn = jax.shard_map(lambda x, y, k: jax.lax.map(one, (x, y, k)),
                           mesh=mesh,
                           in_specs=(P("data"), P("data"), P("data")),
                           out_specs=P("data"), check_vma=False)
        return unpad(fn(xs, ys, keys))

    return _local_update_stack(model, cfg, params, xs, ys, keys)


# --------------------------------------------------------------------------
# the client side of a round, stacked end to end
# --------------------------------------------------------------------------
def cohort_round(model: SplitModel, params: PyTree,
                 clients: List[ClientData], cfg: FLConfig, keys: jax.Array,
                 ledger: CommLedger, num_classes: int, *,
                 mesh: Optional[Mesh] = None,
                 chunk_size: Optional[int] = None,
                 channel=None, client_ids=None):
    """Everything the cohort's clients do in one round — chunked/sharded
    Extract&Selection plus the stacked LocalUpdate — with the same
    transport-charged ledger accounting as ``rounds.client_round``: the
    gathered (sel_acts, sel_y, valid) triple is encoded through the cohort
    entry of the transport ``channel`` (one vmapped quantize for the int8
    codec — the stack never unbatches for the hot path, only for framing),
    each UpperUpdate frame is charged per client at its exact size, and the
    metadata handed to the server is the DECODED wire content (None where a
    faulty channel lost the frame). Returns per-client lists
    (params, metadata, loss) interchangeable with the sequential loop's —
    including byte-identical ledger totals, and identical injected faults:
    the channel keys its randomness on the GLOBAL ``client_ids``, not on
    engine call order."""
    from repro.fl import transport as T
    assert cfg.use_selection, (
        "cohort_round implements the selection path only; the Table-2 "
        "upload-everything baseline (use_selection=False) runs through the "
        "sequential client_round loop")
    stacked = cohort_arrays(clients)
    assert stacked is not None, "cohort_round requires a stackable cohort"
    xs, ys = stacked
    b = len(clients)
    if chunk_size is None:
        chunk_size = cfg.selection_chunk_size
    if chunk_size <= 0:
        chunk_size = auto_chunk_size(
            model, params, xs.shape[1:], xs.dtype, b,
            data_axis=data_axis_size(mesh))

    if channel is None:
        channel = T.Channel(ledger, checksum=cfg.transport_checksum)
    if client_ids is None:
        client_ids = list(range(b))

    with obs.span("select", clients=b) as ssp:
        sel_acts, sel_ys, valid, lloyd_iters = select_cohort(
            model, params, xs, ys, keys, cfg, num_classes,
            chunk_size=chunk_size, mesh=mesh, gather=True)
        ssp.sync(valid)
        if ssp.enabled:
            from repro.core.rounds import emit_selection_sketch
            vnp = np.asarray(valid)
            ssp.set(selected=int(vnp.sum()),
                    lloyd_iters=np.asarray(lloyd_iters).tolist())
            for i, cid in enumerate(client_ids):
                emit_selection_sketch(vnp[i], num_classes,
                                      cfg.clusters_per_class, int(cid),
                                      xs[i].shape[0])

    with obs.span("transport", clients=b) as tsp:
        metadatas = tsp.sync(channel.upload_knowledge_batched(
            [int(c) for c in client_ids], sel_acts, sel_ys, valid,
            T.knowledge_codec(cfg)))

    with obs.span("local_update", clients=b) as lsp:
        cparams, losses = local_update_cohort(model, params, xs, ys, keys,
                                              cfg, mesh=mesh)
        lsp.sync(cparams)
    client_params = [jax.tree.map(lambda a, i=i: a[i], cparams)
                     for i in range(b)]
    with obs.span("transport", clients=b):
        for cid, p in zip(client_ids, client_params):
            channel.upload_update(int(cid), p)
    return client_params, metadatas, [float(l) for l in np.asarray(losses)]


def run_round_distributed(model: SplitModel, global_params: PyTree,
                          upper_init: PyTree, clients: List[ClientData],
                          cfg: FLConfig, key: jax.Array,
                          ledger: Optional[CommLedger] = None,
                          num_classes: int = 10,
                          mesh: Optional[Mesh] = None):
    """Algorithm 1 with the client side stacked (``cohort_round``) and the
    seed's server side (``rounds.server_round``) — bit-identical to
    ``rounds.run_round`` on the same key. Requires ``cfg.use_selection``
    and a stackable cohort (callers fall back to the sequential loop
    otherwise)."""
    from repro.core import rounds as R
    ledger = ledger if ledger is not None else CommLedger()
    keys = jax.random.split(key, len(clients) + 1)
    client_params, metadatas, losses = cohort_round(
        model, global_params, clients, cfg, keys[:-1], ledger, num_classes,
        mesh=mesh)
    res = R.server_round(model, global_params, upper_init, client_params,
                         metadatas, cfg, keys[-1])
    res.client_losses = losses
    res.total_samples = sum(len(c.data) for c in clients)
    return res
