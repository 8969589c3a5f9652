"""The charging surface between the round engines and the wire.

``rounds.client_round``, ``distributed.cohort_round`` and
``FLServer.broadcast_weights`` talk to a :class:`Channel` instead of
estimating sizes: every method builds (or arithmetically sizes) the real
frame, charges the CommLedger with ``len(wire)`` — the exact bytes — and
hands back what the RECEIVER decodes, so a lossy codec's effect on
MetaTraining is observable end to end, not just its byte count.

``Channel`` is the PERFECT wire: every frame arrives intact, exactly once.
``repro.fl.faults.FaultyChannel`` subclasses it to inject deterministic
client crashes, bit-flips, truncations and duplicate deliveries between
``encode`` and ``decode`` — the round engines cannot tell the difference,
which is what keeps the zero-fault path bit-identical to a channel-less
run (ledger included: the perfect channel charges the same arithmetic
frame sizes as the historical module-level helpers, which remain below as
thin wrappers).

``upload_knowledge_batched`` is the stacked-cohort entry: for the int8
codec it runs ONE vmapped quantize over the gathered
``(sel_acts, sel_y, valid)`` triple (the Pallas kernel or its oracle —
bit-identical), then frames each client's bytes from the pre-quantized
levels; the per-client and batched encodings produce identical wire bytes,
which is what keeps the sequential and distributed simulator paths
ledger-equal.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.fl.comms import CommLedger
from repro.fl.transport.codecs import (Int8Codec, Quantized, TensorCodec,
                                       get_codec)
from repro.fl.transport.messages import SelectedKnowledge, pytree_frame_nbytes

PyTree = Any


class Channel:
    """The perfect wire: encode -> charge exact bytes -> decode, every
    frame delivered intact exactly once. ``checksum`` appends the v2 CRC32
    trailer to every frame (4 bytes/frame in the ledger); off by default so
    the fault-free ledger stays byte-identical to the pre-checksum wire.

    The fault-tolerance surface (overridden by ``FaultyChannel``):
    ``begin_round`` resets per-round state, ``update_arrived`` reports
    whether a client's UpperUpdate frame decoded (always True here), and
    ``round_stats`` returns the per-round fault counters (all zero here).
    """

    def __init__(self, ledger: CommLedger, checksum: bool = False):
        self.ledger = ledger
        self.checksum = checksum

    # ---- fault surface (no-ops on the perfect wire) ----
    def begin_round(self, round_idx: int) -> None:
        """Reset per-round wire state. A no-op on the perfect wire; fault
        models key their RNG streams and fate tables off ``round_idx``."""
        pass

    def update_arrived(self, client_id: int) -> bool:
        """Whether ``client_id``'s UpperUpdate frame decoded this round —
        the per-client bit behind the arrival mask in Eq. 2."""
        return True

    def round_stats(self) -> dict:
        """Per-round fault counters (see ``FaultyChannel``); the perfect
        wire reports zeros so callers need not special-case it."""
        return {"corruptions_detected": 0, "retransmits": 0,
                "duplicates": 0, "silent_corruptions": 0,
                "injected_corruptions": 0, "lost_frames": 0,
                "backoff_s": 0.0}

    def decoded_update(self, client_id: int) -> Optional[PyTree]:
        """The update pytree as the server decoded it, when the channel
        had to materialize one (None on the perfect wire: the frame is
        lossless and intact, so the in-memory params ARE the decode)."""
        return None

    # ---- the three frame kinds ----
    def broadcast_weights(self, params: PyTree, num_clients: int) -> int:
        """server -> cohort: one WeightBroadcast frame per member, charged
        at its exact encoded size (native dtypes — a bf16 model costs half
        an f32 model, where the old ``size * 4`` billed both the same).
        The length is computed from leaf shapes/dtypes
        (``pytree_frame_nbytes`` == ``len(encode())``) — the simulator's
        receiver reads the in-memory params, so serializing the full model
        just to measure it would be a per-round device->host copy for
        nothing."""
        nbytes = pytree_frame_nbytes(params, checksum=self.checksum)
        self.ledger.download("weights", nbytes * num_clients,
                             frames=num_clients)
        return nbytes * num_clients

    def upload_update(self, client_id: int, params: PyTree) -> bool:
        """client -> server: the UpperUpdate frame for Eq. 2. Returns
        whether it arrived (always True on the perfect wire; the bytes are
        shape/dtype-computed, same rationale as ``broadcast_weights``)."""
        nbytes = pytree_frame_nbytes(params, checksum=self.checksum)
        self.ledger.upload("weights", nbytes)
        return True

    def upload_knowledge(self, client_id: int, acts, labels, valid,
                         codec: TensorCodec,
                         pre: Optional[Quantized] = None) -> Optional[Tuple]:
        """client -> server: encode the selection triple, charge the exact
        frame bytes, and return what the server DECODES from the wire
        (valid rows only, dequantized f32) — the metadata MetaTraining
        sees. None means the frame never arrived (faulty channels only)."""
        with obs.span("encode", frame="knowledge", client=int(client_id)):
            wire = SelectedKnowledge(acts, labels, valid, codec,
                                     pre=pre).encode(checksum=self.checksum)
        self.ledger.upload("metadata", len(wire))
        with obs.span("decode", frame="knowledge", client=int(client_id)):
            return SelectedKnowledge.decode(wire)

    def upload_knowledge_batched(self, client_ids: Sequence[int], sel_acts,
                                 sel_ys, valid,
                                 codec: TensorCodec) -> List[Optional[Tuple]]:
        """Stacked-cohort knowledge upload: encode every client's frame
        (int8 quantize runs once, vmapped, over the whole stack), charge
        each frame's exact bytes, and return the per-client decoded
        triples (None per client whose frame was lost)."""
        with obs.span("encode", frame="knowledge", clients=len(client_ids)):
            pres = prequantize_cohort(codec, jnp.asarray(sel_acts),
                                      jnp.asarray(valid))
        out = []
        for i, cid in enumerate(client_ids):
            out.append(self.upload_knowledge(
                cid, sel_acts[i], sel_ys[i], valid[i], codec,
                pre=None if pres is None else pres[i]))
        return out


# --------------------------------------------------------------------------
# module-level helpers — the historical API, kept as thin perfect-wire
# wrappers (tests and external callers use these directly)
# --------------------------------------------------------------------------
def broadcast_weights(ledger: CommLedger, params: PyTree,
                      num_clients: int) -> int:
    """Perfect-wire ``Channel.broadcast_weights`` (exact per-member frame
    bytes charged to ``ledger``); returns total bytes charged."""
    return Channel(ledger).broadcast_weights(params, num_clients)


def upload_update(ledger: CommLedger, params: PyTree) -> int:
    """client -> server: the UpperUpdate frame for Eq. 2. Returns bytes
    (shape/dtype-computed)."""
    nbytes = pytree_frame_nbytes(params)
    ledger.upload("weights", nbytes)
    return nbytes


def upload_knowledge(ledger: CommLedger, acts, labels, valid,
                     codec: TensorCodec,
                     pre: Optional[Quantized] = None) -> Tuple:
    """Perfect-wire ``Channel.upload_knowledge`` for a single client:
    encode, charge exact frame bytes, return the decoded triple."""
    return Channel(ledger).upload_knowledge(0, acts, labels, valid, codec,
                                            pre=pre)


def prequantize_cohort(codec: TensorCodec, sel_acts: jnp.ndarray,
                       valid: jnp.ndarray) -> Optional[List[Quantized]]:
    """One compiled (vmappable) quantize over a stacked cohort's gathered
    triple: (B, CK, ...) acts + (B, CK) valid -> per-client Quantized, or
    None for codecs with no quantize stage. Per-client statistics are
    reductions over each client's own rows, so the vmapped result is
    bit-identical to B separate quantizes — same wire bytes either way."""
    if not isinstance(codec, Int8Codec):
        return None
    b, ck = sel_acts.shape[0], sel_acts.shape[1]
    flat = jnp.reshape(sel_acts, (b, ck, -1)).astype(jnp.float32)
    m = jnp.asarray(valid).astype(bool)
    if codec.use_pallas:
        from repro.kernels.ops import quantize_affine
        q, xmin, scale = jax.vmap(quantize_affine)(flat, m)
    else:
        from repro.kernels.ref import quantize_affine_ref
        q, xmin, scale = jax.vmap(quantize_affine_ref)(flat, m)
    q, xmin, scale = np.asarray(q), np.asarray(xmin), np.asarray(scale)
    return [Quantized(q[i], float(xmin[i]), float(scale[i]))
            for i in range(b)]


def upload_knowledge_batched(ledger: CommLedger, sel_acts, sel_ys, valid,
                             codec: TensorCodec) -> List[Tuple]:
    """Perfect-wire ``Channel.upload_knowledge_batched`` over a stacked
    cohort (clients numbered 0..B-1): one vmapped quantize, per-frame
    exact byte charges, per-client decoded triples."""
    return Channel(ledger).upload_knowledge_batched(
        range(np.asarray(valid).shape[0]), sel_acts, sel_ys, valid, codec)


def knowledge_codec(cfg) -> TensorCodec:
    """The codec an FLConfig asks for (``transport_codec`` knob; the Pallas
    quantize engine rides the same ``use_pallas_selection`` switch as the
    selection kernels — one hot-path toggle for the whole client side)."""
    return get_codec(cfg.transport_codec,
                     use_pallas=cfg.use_pallas_selection)
