"""Algorithm 1 (paper): one global round of Split Training with Metadata
Selection, at simulator granularity. The pod-scale engine — ``shard_map``
over the mesh's data axis, chunked mega-cohort streaming, and the stacked
LocalUpdate — lives in ``repro.core.distributed``; this module
delegates to it when ``cfg.distributed_selection`` is set (and for the
chunked path whenever a cohort's stack would exceed the one-device memory
budget). Extract&Selection — the hot path — is batched either way: when the
cohort's data shapes agree, ``select_for_clients`` stacks the clients and
runs the lower forward plus the whole §3.1 pipeline under one ``vmap``.

    for each client k:
        M_Ck loads W_G(t-1)
        D_Mk(t)  <- Extract&Selection(D_k, W_G^l(t-1))          # §3.1
        W_Ck(t)  <- LocalUpdate(D_k, W_G(t-1))                  # §3.2
    server:
        D_M(t)   <- U_k D_Mk(t)
        W_S^u(t) <- MetaTraining(D_M(t), W_G^u(0))              # §3.3
        M_COM(t) <- ModelCompose(W_G^l(t-1), W_S^u(t))
        test M_COM(t)
        W_G(t)   <- WeightAverage(W_Ck(t))                      # Eq. 2
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FLConfig
from repro.core import fedavg as fa
from repro.core import meta_training as mt
from repro.core.selection import Selection, select_metadata
from repro.core.split import SplitModel
from repro.data.partition import ClientData
from repro.fl.comms import CommLedger
from repro.obs.profile import profiled_jit
from repro.optim import sgd

PyTree = Any

# Batched selection stacks a chunk of the cohort's data + activations on one
# device; past this many stacked input elements (~1 GiB f32) the cohort is
# STREAMED through the pipeline in client chunks sized to fit the budget
# (repro.core.distributed.select_cohort) — chunking is a pure schedule, so
# results stay bit-identical to the one-stack (and sequential) path.
MAX_BATCHED_ELEMENTS = 1 << 28


@dataclass
class RoundResult:
    global_params: PyTree            # W_G(t)
    composed_params: PyTree          # M_COM(t)
    upper_trained: PyTree            # W_S^u(t)
    metadata_count: int              # |D_M(t)|
    total_samples: int               # sum_k |D_k|
    client_losses: List[float] = field(default_factory=list)
    meta_losses: Optional[np.ndarray] = None


def select_for_clients(model: SplitModel, params: PyTree,
                       clients: List[ClientData], cfg: FLConfig,
                       keys: jax.Array, num_classes: int, mesh=None):
    """Batched Extract&Selection: stack the cohort, vmap the lower forward
    and the whole §3.1 pipeline across clients in one call — replacing the
    per-client Python loop's selections. ``keys`` are the per-client round
    keys; each client's selection key matches what ``client_round`` would
    derive on its own, so batched and sequential rounds are identical.

    A cohort whose stacked inputs + activations exceed MAX_BATCHED_ELEMENTS
    (or with ``cfg.selection_chunk_size`` set) is streamed through the
    pipeline in client chunks by ``distributed.select_cohort`` — identical
    results, with each chunk's activations/features gathered down to the
    selected metadata and dropped before the next chunk runs. ``mesh`` (a
    mesh with a ``data`` axis) shards the client axis across devices with
    ``shard_map``.

    Returns a list of (x_k, y_k, (sel_acts_k, sel_y_k, valid_k),
    lloyd_iters_k) per
    client (device-resident, so ``client_round`` neither re-transfers nor
    re-selects), or None when selection/batching is off or the cohort is
    ragged (different data shapes) — callers then fall back to the
    sequential path."""
    from repro.core import distributed as D
    if not cfg.use_selection or not cfg.batched_selection:
        return None
    if not D.cohort_is_stackable(clients):
        return None
    if not D.cohort_inputs_fit(clients):
        return None
    x_shape = clients[0].data.x.shape
    x_dtype = jax.dtypes.canonicalize_dtype(
        np.asarray(clients[0].data.x).dtype)
    chunk = cfg.selection_chunk_size
    if chunk <= 0:
        chunk = D.auto_chunk_size(model, params, x_shape, x_dtype,
                                  len(clients),
                                  data_axis=D.data_axis_size(mesh))
    xs, ys = D.cohort_arrays(clients)
    with obs.span("select", clients=len(clients), batched=True) as ssp:
        sel_acts, sel_ys, valid, lloyd_iters = D.select_cohort(
            model, params, xs, ys, keys, cfg, num_classes, chunk_size=chunk,
            mesh=mesh, gather=True)
        ssp.sync(valid)
        if ssp.enabled:
            vnp = np.asarray(valid).astype(bool)
            total = int(np.prod(x_shape[:1])) * len(clients)
            ssp.set(selected=int(vnp.sum()),
                    selected_fraction=float(vnp.sum()) / max(total, 1),
                    lloyd_iters=int(np.asarray(lloyd_iters).min()))
    return [(xs[i], ys[i], (sel_acts[i], sel_ys[i], valid[i]),
             lloyd_iters[i])
            for i in range(len(clients))]


def emit_selection_sketch(valid, num_classes: int, clusters_per_class: int,
                          client_id: int, n_k: int) -> None:
    """Persist one client's selection sketch into the trace: the class x
    cluster occupancy bitmap (which §3.1 slots produced a representative)
    plus the selected fraction |D_Mk|/|D_k|. Emitted BEFORE the transport
    encode — the wire compacts the bitmap to the valid rows, so this is
    the only place the (CK,) slot structure still exists. The event nests
    under the open ``select`` span, so the round index is its ancestry."""
    v = np.asarray(valid).astype(bool).reshape(-1)
    if v.size != num_classes * clusters_per_class:
        return   # Table-2 baseline ships a per-sample mask, not slots
    obs.event("selection_sketch", client=int(client_id),
              occupancy=v.reshape(num_classes,
                                  clusters_per_class).astype(int).tolist(),
              selected=int(v.sum()),
              selected_fraction=float(v.sum() / max(n_k, 1)))


def epoch_permutations(key: jax.Array, n: int, epochs: int) -> jnp.ndarray:
    """(epochs, n) shuffle orders for LocalUpdate: epoch 0 keeps the seed's
    stream (``permutation(key, n)``); every later epoch folds its index into
    the key for a FRESH permutation. (The seed replayed epoch 0's order
    every epoch via ``jnp.tile`` — multi-epoch SGD saw one fixed batch
    order.)"""
    ks = jax.vmap(lambda e: jax.random.fold_in(key, e))(jnp.arange(epochs))
    ks = ks.at[0].set(key)
    return jax.vmap(lambda k: jax.random.permutation(k, n))(ks)


def local_batches(x: jnp.ndarray, y: jnp.ndarray, k_loc: jax.Array,
                  cfg: FLConfig):
    """Shuffle + batch one client's data for LocalUpdate: (steps, bs, ...)
    with a fresh permutation each local epoch. Shared by the sequential
    ``client_round`` and the stacked ``distributed.local_update_cohort`` so
    both paths batch identically."""
    n = x.shape[0]
    bs = min(cfg.local_batch_size, n)
    steps_per_epoch = max(n // bs, 1)
    perm = epoch_permutations(k_loc, n, cfg.local_epochs)
    perm = perm[:, :steps_per_epoch * bs].reshape(-1)
    bx = x[perm].reshape((-1, bs) + x.shape[1:])
    by = y[perm].reshape(-1, bs)
    return bx, by


def client_local_update(model: SplitModel, cfg: FLConfig, params: PyTree,
                        x: jnp.ndarray, y: jnp.ndarray, k_loc: jax.Array):
    """One client's LocalUpdate (§3.2) from the global ``params``: shuffle
    and batch with ``k_loc``, then the SGD scan. Returns (new params,
    per-step losses). The sequential ``client_round`` runs it compiled
    (``_local_update_client``); the stacked engines map it over clients."""
    bx, by = local_batches(x, y, k_loc, cfg)
    opt = sgd(cfg.local_lr)
    new_params, _, losses = fa.local_update(
        params, opt, opt.init(params), (bx, by),
        lambda p, b: model.loss(p, b))
    return new_params, losses


# one compiled LocalUpdate shared by every client and round whose shapes
# agree (an eager scan traces and compiles the model's SGD loop per call)
_local_update_client = profiled_jit(client_local_update,
                                    name="local_update_client",
                                    static_argnames=("model", "cfg"))


def client_round(model: SplitModel, params: PyTree, client: ClientData,
                 cfg: FLConfig, key: jax.Array, ledger: CommLedger,
                 num_classes: int, precomputed=None, channel=None,
                 client_id: int = 0):
    """Client k's work: Extract&Selection + LocalUpdate. ``precomputed`` is
    an optional (x, y, (sel_acts, sel_y, valid)) tuple from
    ``select_for_clients`` (already on device).

    Both uploads flow through a transport ``channel`` (a perfect wire by
    default; ``repro.fl.faults.FaultyChannel`` injects crashes/corruption):
    the ledger is charged the exact frame bytes, and the metadata handed
    back is what the server DECODES (valid rows only, dequantized under a
    lossy ``cfg.transport_codec``) — or None when the frame never survived
    the wire. ``client_id`` is the client's GLOBAL index: the fault
    runtime keys its per-(round, client) randomness on it, which is what
    makes injected faults identical across engines."""
    from repro.fl import transport as T
    if channel is None:
        channel = T.Channel(ledger, checksum=cfg.transport_checksum)
    lloyd_it = None
    if precomputed is not None:
        if len(precomputed) == 4:      # select_for_clients adds lloyd_iters
            x, y, metadata, lloyd_it = precomputed
        else:
            x, y, metadata = precomputed
    else:
        x, y = jnp.asarray(client.data.x), jnp.asarray(client.data.y)
        metadata = None
    k_sel, k_loc = jax.random.split(key)

    with obs.span("client", client=int(client_id)) as csp:
        # ---- Extract & Selection (uses ONLY the lower part W_G^l(t-1)) --
        codec = T.knowledge_codec(cfg)
        with obs.span("select") as ssp:
            if cfg.use_selection:
                if metadata is None:
                    acts = model.apply_lower(params, x)           # A_k^[j]
                    sel = select_metadata(
                        acts, y, k_sel, num_classes=num_classes,
                        clusters_per_class=cfg.clusters_per_class,
                        pca_components=cfg.pca_components,
                        kmeans_iters=cfg.kmeans_iters,
                        use_pallas=cfg.use_pallas_selection,
                        pca_solver=cfg.pca_solver)
                    metadata = (jnp.take(acts, sel.indices, axis=0),
                                jnp.take(y, sel.indices, axis=0), sel.valid)
                    lloyd_it = sel.lloyd_iters
                if ssp.enabled:
                    emit_selection_sketch(metadata[2], num_classes,
                                          cfg.clusters_per_class,
                                          client_id, x.shape[0])
                metadata = ssp.sync(
                    channel.upload_knowledge(client_id, *metadata, codec))
            else:
                # Table 2 baseline: ALL activation maps are uploaded.
                acts = model.apply_lower(params, x)
                metadata = ssp.sync(channel.upload_knowledge(
                    client_id, acts, y, jnp.ones((x.shape[0],), bool),
                    codec))
            if ssp.enabled and metadata is not None:
                n_sel = int(np.asarray(metadata[2]).sum())
                ssp.set(selected=n_sel,
                        selected_fraction=n_sel / max(x.shape[0], 1))
                if lloyd_it is not None:
                    ssp.set(lloyd_iters=int(lloyd_it))

        # ---- LocalUpdate ----
        with obs.span("local_update") as lsp:
            new_params, losses = _local_update_client(model, cfg, params,
                                                      x, y, k_loc)
            lsp.sync(new_params)
            if lsp.enabled:
                lsp.set(steps=int(losses.shape[0]))
        channel.upload_update(client_id, new_params)
        if csp.enabled:
            csp.set(samples=int(x.shape[0]))
    return new_params, metadata, float(losses.mean())


def server_round(model: SplitModel, prev_global: PyTree, upper_init: PyTree,
                 client_params: List[PyTree], metadatas: List[tuple],
                 cfg: FLConfig, key: jax.Array,
                 fedavg_weights: Optional[List[float]] = None) -> RoundResult:
    """Server's work: aggregate metadata, MetaTraining, ModelCompose, Eq. 2.

    ``metadatas`` are the DECODED SelectedKnowledge triples — the transport
    layer sends valid slots only, so per-client row counts vary (and can be
    zero for a client whose every cluster came back empty). A ``None``
    entry is a frame that never survived the wire (client crash or an
    exhausted retry budget): the server aggregates over exactly the
    knowledge that ARRIVED."""
    arrived = [m for m in metadatas if m is not None]
    if arrived:
        acts = jnp.concatenate([m[0] for m in arrived], 0)
        ys = jnp.concatenate([m[1] for m in arrived], 0)
        valid = jnp.concatenate([m[2] for m in arrived], 0)
        nmeta = int(valid.sum())
    else:
        acts = ys = valid = None
        nmeta = 0

    if acts is None or acts.shape[0] == 0:
        # nothing arrived: W_S^u(t) stays W_G^u(0)
        upper, meta_losses = upper_init, jnp.zeros((0,))
    else:
        with obs.span("meta_train", rows=int(acts.shape[0])) as msp:
            upper, meta_losses = mt.meta_train(
                upper_init, model.upper_loss, acts, ys,
                epochs=cfg.meta_epochs, batch_size=cfg.meta_batch_size,
                lr=cfg.meta_lr, l2=cfg.meta_l2, key=key, valid=valid)
            msp.sync(upper)

    # ModelCompose: lower layers from W_G^l(t-1), upper from W_S^u(t)
    composed = model.merge(model.split(prev_global)[0], upper)
    # Eq. 2, renormalized over the clients that count: 0-weight clients
    # straggled past FLServer.deadline or never delivered an update frame;
    # None = every client counts, the exact unweighted mean — bit-identical
    # to the no-deadline perfect-wire path. A round where NO update counts
    # (every client crashed/lost) keeps W_G(t-1): averaging nothing must
    # not destroy the model.
    if fedavg_weights is not None and not any(fedavg_weights):
        new_global = prev_global
    elif not client_params:
        new_global = prev_global
    else:
        new_global = fa.weight_average(client_params,
                                       weights=fedavg_weights)
    return RoundResult(
        global_params=new_global, composed_params=composed,
        upper_trained=upper, metadata_count=nmeta,
        total_samples=0, meta_losses=np.asarray(meta_losses))


def run_cohort(model: SplitModel, params: PyTree,
               clients: List[ClientData], cfg: FLConfig, keys: jax.Array,
               ledger: CommLedger, num_classes: int, mesh=None,
               channel=None, client_ids=None):
    """The client side of one round for a whole cohort, with the engine
    dispatch in ONE place (shared by ``run_round`` and ``FLSimulation``):
    the stacked pod engine (``distributed.cohort_round``) when configured
    and the cohort stacks within budget, else the per-client loop with
    batched-selection precompute. Returns per-client lists
    (params, metadata, loss) — metadata entries are None for frames that
    did not survive a faulty ``channel``.

    ``client_ids`` are the cohort members' GLOBAL indices (defaults to
    cohort position): the fault runtime draws each client's faults from
    (seed, round, id) streams, so whichever engine runs the round — and in
    whatever order — the same clients crash and the same frames corrupt."""
    from repro.core import distributed as D
    if client_ids is None:
        client_ids = list(range(len(clients)))
    if (cfg.distributed_selection and cfg.use_selection
            and D.cohort_is_stackable(clients)
            and D.cohort_inputs_fit(clients)):
        return D.cohort_round(model, params, clients, cfg, keys, ledger,
                              num_classes, mesh=mesh, channel=channel,
                              client_ids=client_ids)
    pre = select_for_clients(model, params, clients, cfg, keys,
                             num_classes, mesh=mesh)
    client_params, metadatas, losses = [], [], []
    for i, (c, k) in enumerate(zip(clients, keys)):
        p, m, l = client_round(model, params, c, cfg, k, ledger,
                               num_classes,
                               precomputed=None if pre is None else pre[i],
                               channel=channel,
                               client_id=int(client_ids[i]))
        client_params.append(p)
        metadatas.append(m)
        losses.append(l)
    return client_params, metadatas, losses


def run_round(model: SplitModel, global_params: PyTree, upper_init: PyTree,
              clients: List[ClientData], cfg: FLConfig, key: jax.Array,
              ledger: Optional[CommLedger] = None,
              num_classes: int = 10, mesh=None) -> RoundResult:
    ledger = ledger if ledger is not None else CommLedger()
    keys = jax.random.split(key, len(clients) + 1)
    client_params, metadatas, losses = run_cohort(
        model, global_params, clients, cfg, keys[:-1], ledger, num_classes,
        mesh=mesh)
    res = server_round(model, global_params, upper_init, client_params,
                       metadatas, cfg, keys[-1])
    res.client_losses = losses
    res.total_samples = sum(len(c.data) for c in clients)
    return res
