"""The plain reference of a synchronous split-FL round, and the comparison
that decides ``correct``.

It imports nothing of the program. It follows the published description:

- WRN-d-k (Zagoruyko & Komodakis, arXiv:1605.07146): a 3x3 stem of 16
  channels, three groups of (d-4)/6 pre-activation basic blocks of widths
  16k, 32k, 64k (strides 1, 2, 2), a 1x1 projection shortcut where the width
  changes, then BN-ReLU, global average pooling and a linear head. Departure,
  shared with the program and noted in its model: batch normalisation uses
  the batch's statistics in training and evaluation alike (no running
  averages), and there is no dropout.
- The round (arXiv:2111.03512, Algorithm 1): each client of the cohort maps
  its data through the lower part ``W_G^l(t-1)``; exact PCA of the flattened
  maps to ``pca_components`` features (Gram matrix, eigendecomposed on the
  host in float64); per class, ``clusters_per_class`` K-means clusters
  (farthest-point initialisation from one random row of the class, then
  Lloyd sweeps); per cluster the row closest to its centre. The knowledge
  is those rows' maps, sent through the per-tensor affine int8 codec
  (``x_hat = (q + 128) * scale + xmin``, ``scale = (max - min) / 255`` over
  the rows sent). LocalUpdate is SGD over one shuffled pass in batches.
  The server meta-trains the upper part from ``W_G^u(0)`` on the decoded
  knowledge and averages the clients' weights (Eq. 2).
- The random stream is the one the program documents for a seeded round:
  per round ``key, k_round, k_sample = split(key, 3)``; the cohort is
  ``choice(k_sample, clients, (m,), replace=False)``; client ``j`` gets
  ``split(k_round, m)[j]``, split once more into its selection key (split
  again per class) and its shuffle key; the server's key is
  ``fold_in(k_round, m)``, split once per meta epoch. Without it a seeded
  comparison of two stochastic trainings could not be made.

Everything is float32, and matrix products run at the configuration's
matmul precision (``highest``). ``precision="high"`` (three bfloat16
passes) is the control: the same reference in the nearest precision below
the one the configuration states.

``compare`` follows the program's first rounds and returns the numbers that
``correct`` is judged by. For MetaTraining and the codec it reads which
samples the program picked (by the nearest of the reference's own maps to
each decoded row) and feeds the reference those samples, as a served
model's check feeds the reference the served tokens: one differing pick then
shows in ``select_miss`` alone and not again in every later number.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = {"highest": HIGHEST, "high": jax.lax.Precision.HIGH,
              "default": jax.lax.Precision.DEFAULT}


# --------------------------------------------------------------------------
# WRN
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class Arch:
    depth: int
    widen: int
    num_classes: int
    image_size: int
    channels: int
    split_group: int
    precision: str = "highest"

    @property
    def blocks(self) -> int:
        return (self.depth - 4) // 6

    @property
    def widths(self):
        return (16, 16 * self.widen, 32 * self.widen, 64 * self.widen)

    @property
    def prec(self):
        return PRECISIONS[self.precision]


def arch_from(config: Dict[str, Any], precision: str = "") -> Arch:
    """The reference's architecture at the configuration's matmul
    precision, or at ``precision`` (the control's)."""
    m = config["model"]
    return Arch(depth=m["depth"], widen=m["widen"],
                num_classes=m["num_classes"], image_size=m["image_size"],
                channels=m["channels"], split_group=m["split_group"],
                precision=precision or config["precision"]["matmul"])


def init_params(arch: Arch, key: jax.Array):
    """He-normal convolutions, unit BN, a 1/sqrt(fan_in) normal head, in the
    program's parameter layout. Call under ``jax.jit``."""
    keys = iter(jax.random.split(key, 4 + 3 * 3 * arch.blocks))

    def conv(kh, cin, cout):
        return (jax.random.normal(next(keys), (kh, kh, cin, cout))
                * math.sqrt(2.0 / (kh * kh * cin)))

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    w = arch.widths
    p = {"conv_in": conv(3, arch.channels, w[0])}
    for g in range(3):
        blocks = []
        for b in range(arch.blocks):
            cin = w[g] if b == 0 else w[g + 1]
            blk = {"bn1": bn(cin), "conv1": conv(3, cin, w[g + 1]),
                   "bn2": bn(w[g + 1]), "conv2": conv(3, w[g + 1], w[g + 1])}
            if cin != w[g + 1]:
                blk["shortcut"] = conv(1, cin, w[g + 1])
            blocks.append(blk)
        p[f"group{g + 1}"] = blocks
    p["bn_out"] = bn(w[3])
    p["fc_w"] = (jax.random.normal(next(keys), (w[3], arch.num_classes))
                 / math.sqrt(w[3]))
    p["fc_b"] = jnp.zeros((arch.num_classes,))
    return p


def lower_keys(arch: Arch):
    return ["conv_in"] + [f"group{g}" for g in range(1, arch.split_group + 1)]


def split(arch: Arch, params):
    lk = lower_keys(arch)
    return ({k: v for k, v in params.items() if k in lk},
            {k: v for k, v in params.items() if k not in lk})


def _conv(arch, x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=arch.prec)


def _bn(x, p, eps=1e-5):
    mean = x.mean((0, 1, 2))
    var = x.var((0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _block(arch, p, x, stride):
    h = jax.nn.relu(_bn(x, p["bn1"]))
    sc = _conv(arch, h, p["shortcut"], stride) if "shortcut" in p else x
    h = _conv(arch, h, p["conv1"], stride)
    h = jax.nn.relu(_bn(h, p["bn2"]))
    return _conv(arch, h, p["conv2"], 1) + sc


def _group(arch, blocks, x, stride):
    for b, p in enumerate(blocks):
        x = _block(arch, p, x, stride if b == 0 else 1)
    return x


def lower(arch: Arch, params, x):
    h = _conv(arch, x, params["conv_in"], 1)
    for g in range(1, arch.split_group + 1):
        h = _group(arch, params[f"group{g}"], h, 1 if g == 1 else 2)
    return h


def upper(arch: Arch, params, h):
    for g in range(arch.split_group + 1, 4):
        h = _group(arch, params[f"group{g}"], h, 2)
    h = jax.nn.relu(_bn(h, params["bn_out"])).mean((1, 2))
    return jnp.dot(h, params["fc_w"], precision=arch.prec) + params["fc_b"]


def _xent(logits, y):
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, y[:, None], -1)[:, 0]


# --------------------------------------------------------------------------
# LocalUpdate and MetaTraining
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("arch", "bs", "epochs", "lr",
                                             "half"))
def local_update(arch: Arch, params, x, y, key, *, bs, epochs, lr,
                 half=False):
    """SGD over ``epochs`` shuffled passes -> (params, per-step losses).
    Epoch 0 shuffles with ``key``, epoch e > 0 with ``fold_in(key, e)``.
    ``half`` is a planted fault: each step's loss is the mean over the
    first half of its batch."""
    n = x.shape[0]
    bs = min(bs, n)
    steps = max(n // bs, 1)
    perms = [jax.random.permutation(
        key if e == 0 else jax.random.fold_in(key, e), n)[:steps * bs]
        for e in range(epochs)]
    perm = jnp.concatenate(perms)
    bx = x[perm].reshape((-1, bs) + x.shape[1:])
    by = y[perm].reshape(-1, bs)
    if half:
        bx, by = bx[:, :bs // 2], by[:, :bs // 2]

    def loss(p, b):
        return _xent(upper(arch, p, lower(arch, p, b[0])), b[1]).mean()

    def step(p, b):
        l, g = jax.value_and_grad(loss)(p, b)
        return jax.tree.map(lambda a, ga: a - lr * ga, p, g), l

    return jax.lax.scan(step, params, (bx, by))


@functools.partial(jax.jit, static_argnames=("arch", "bs", "epochs", "lr",
                                             "l2"))
def meta_train(arch: Arch, upper0, acts, ys, key, *, bs, epochs, lr, l2):
    """SGD on the upper part from ``upper0`` over the knowledge rows."""
    m = acts.shape[0]
    bs = min(bs, m)
    steps = max(m // bs, 1)

    def loss(p, b):
        a, t = b
        l = _xent(upper(arch, p, a), t).mean()
        if l2:
            l = l + l2 * sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                             for v in jax.tree.leaves(p))
        return l

    def step(p, b):
        l, g = jax.value_and_grad(loss)(p, b)
        return jax.tree.map(lambda a, ga: a - lr * ga, p, g), l

    p = upper0
    for ek in jax.random.split(key, epochs):
        perm = jax.random.permutation(ek, m)[:steps * bs]
        a = acts[perm].reshape((steps, bs) + acts.shape[1:])
        t = ys[perm].reshape(steps, bs)
        p, _ = jax.lax.scan(step, p, (a, t))
    return p


# --------------------------------------------------------------------------
# Extract & Selection
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("arch",))
def _maps(arch: Arch, params, x):
    return lower(arch, params, x)


@functools.partial(jax.jit, static_argnames=("arch",))
def _gram(arch: Arch, flat):
    mean = flat.mean(0)
    xc = flat - mean
    return jnp.dot(xc, xc.T, precision=arch.prec) / flat.shape[0], mean


@functools.partial(jax.jit, static_argnames=("arch",))
def _features(arch: Arch, flat, mean, evecs, evals):
    n = flat.shape[0]
    xc = flat - mean
    comps = jnp.dot(xc.T, evecs, precision=arch.prec)
    comps = comps / jnp.sqrt(jnp.maximum(evals * n, 1e-12))
    return jnp.dot(xc, comps, precision=arch.prec)


def _sqdist(arch, x, c):
    return (jnp.sum(x * x, -1)[:, None] + jnp.sum(c * c, -1)[None, :]
            - 2.0 * jnp.dot(x, c.T, precision=arch.prec))


@functools.partial(jax.jit, static_argnames=("arch", "kk", "iters",
                                             "num_classes"))
def _kmeans_pick(arch: Arch, feats, labels, key, *, kk, iters, num_classes):
    """Per class: farthest-point init, ``iters`` Lloyd sweeps, and the row
    nearest each centre -> (indices (C*kk,), valid (C*kk,))."""
    keys = jax.random.split(key, num_classes)

    def one_class(c, k_c):
        mask = labels == c
        first = jax.random.categorical(k_c, jnp.where(mask, 0.0, -jnp.inf))
        cen = jnp.zeros((kk, feats.shape[1]), feats.dtype).at[0].set(
            feats[first])
        dmin = _sqdist(arch, feats, feats[first][None])[:, 0]

        def init_body(i, st):
            cen, dmin = st
            far = jnp.argmax(jnp.where(mask, dmin, -jnp.inf))
            cen = cen.at[i].set(feats[far])
            return cen, jnp.minimum(dmin,
                                    _sqdist(arch, feats, feats[far][None])[:, 0])

        cen, _ = jax.lax.fori_loop(1, kk, init_body, (cen, dmin))

        def assign(cen):
            d = jnp.where(mask[:, None], _sqdist(arch, feats, cen), jnp.inf)
            return jnp.argmin(d, 1), jnp.min(d, 1)

        def sweep(_, cen):
            a, _ = assign(cen)
            oh = (a[:, None] == jnp.arange(kk)[None, :]) & mask[:, None]
            cnt = oh.sum(0)
            sums = jnp.dot(oh.astype(jnp.float32).T, feats,
                           precision=arch.prec)
            newc = sums / jnp.maximum(cnt, 1)[:, None]
            return jnp.where(cnt[:, None] > 0, newc, cen)

        cen = jax.lax.fori_loop(0, iters, sweep, cen)
        a, own = assign(cen)
        same = (a[:, None] == jnp.arange(kk)[None, :]) & mask[:, None]
        idx = jnp.argmin(jnp.where(same, own[:, None], jnp.inf), 0)
        return idx.astype(jnp.int32), same.sum(0) > 0

    idx, valid = jax.vmap(one_class)(jnp.arange(num_classes), keys)
    return idx.reshape(-1), valid.reshape(-1)


def select(arch: Arch, fl: Dict[str, Any], acts, labels, key):
    """-> (indices (C*kk,), valid (C*kk,)) as host arrays."""
    n = acts.shape[0]
    flat = acts.reshape(n, -1).astype(jnp.float32)
    p = min(fl["pca_components"], n - 1 if n > 1 else 1, flat.shape[1])
    g, mean = _gram(arch, flat)
    evals, evecs = np.linalg.eigh(np.asarray(g, np.float64))
    evals = evals[::-1][:p].astype(np.float32)
    evecs = np.ascontiguousarray(evecs[:, ::-1][:, :p]).astype(np.float32)
    feats = _features(arch, flat, mean, jnp.asarray(evecs),
                      jnp.asarray(evals))
    idx, valid = _kmeans_pick(arch, feats, labels, key,
                              kk=fl["clusters_per_class"],
                              iters=fl["kmeans_iters"],
                              num_classes=arch.num_classes)
    return np.asarray(idx), np.asarray(valid)


def int8_roundtrip(rows: np.ndarray):
    """The codec's affine int8 over the rows sent -> (decoded, scale)."""
    x = np.asarray(rows, np.float32).reshape(rows.shape[0], -1)
    lo, hi = np.float32(x.min()), np.float32(x.max())
    rng = np.float32(hi - lo)
    scale = (rng * np.float32(1.0 / 255.0)) if rng > 0 else np.float32(1.0)
    q = np.clip(np.round((x - lo) * (np.float32(1.0) / scale))
                - np.float32(128.0), -128, 127)
    dec = (q + np.float32(128.0)) * scale + lo
    return dec.astype(np.float32).reshape(rows.shape), float(scale)


# --------------------------------------------------------------------------
# records of a round, from the program or from a reference run
# --------------------------------------------------------------------------
@dataclass
class Round:
    cohort: List[int]
    losses: List[float]                  # per client: mean LocalUpdate loss
    knowledge: List[Any]                 # per client: decoded rows (r, ...)
    knowledge_labels: List[Any]          # per client: (r,)
    global_params: Any                   # W_G(t), host arrays
    upper: Any                           # W_S^u(t), host arrays
    picks: List[Any] = field(default_factory=list)  # reference only


def to_host(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


@jax.jit
def _nearest(dec, maps, dec_labels, labels):
    d = (jnp.sum(dec * dec, 1)[:, None] + jnp.sum(maps * maps, 1)[None, :]
         - 2.0 * jnp.dot(dec, maps.T, precision=HIGHEST))
    d = jnp.where(dec_labels[:, None] == labels[None, :], d, jnp.inf)
    return jnp.argmin(d, 1)


def _fl_args(fl):
    return dict(bs=int(fl["local_batch_size"]), epochs=int(fl["local_epochs"]),
                lr=float(fl["local_lr"]))


def _meta_args(fl):
    return dict(bs=int(fl["meta_batch_size"]), epochs=int(fl["meta_epochs"]),
                lr=float(fl["meta_lr"]), l2=float(fl["meta_l2"]))


FAULTS = ("unchanged", "half_batch", "altered_row")


def run_rounds(arch: Arch, fl: Dict[str, Any], clients, w0, key,
               rounds: int, follow: Optional[List[Round]] = None,
               fault: str = "") -> List[Round]:
    """The reference's first ``rounds`` rounds from weights ``w0`` and the
    simulation key ``key``. ``clients`` are (x, y, uid) host arrays, ``uid``
    naming each row's sample so that duplicated samples compare equal.

    With ``follow`` (the records of the run under test), round t's codec and
    MetaTraining take the samples that run picked, read from its decoded
    rows; ``Round.picks`` then holds, per client, (reference uids, followed
    uids, the followed rows as the reference decodes them, their scale).

    ``fault`` plants one of ``FAULTS`` for the check's own tests: a round
    that leaves the weights unchanged, half of each batch left out, or the
    first knowledge row of each client negated where it is produced."""
    m = min(int(fl["clients_per_round"]), len(clients))
    params = jax.tree.map(jnp.asarray, w0)
    upper0 = split(arch, params)[1]
    out = []
    for t in range(rounds):
        key, k_round, k_sample = jax.random.split(key, 3)
        cohort = [int(i) for i in np.asarray(jax.random.choice(
            k_sample, len(clients), (m,), replace=False))]
        keys = jax.random.split(k_round, m)
        k_server = jax.random.fold_in(k_round, m)
        losses, cparams, know, know_y, picks = [], [], [], [], []
        for j, cid in enumerate(cohort):
            x, y, uid = clients[cid]
            k_sel, k_loc = jax.random.split(keys[j])
            xd, yd = jnp.asarray(x), jnp.asarray(y)
            acts = _maps(arch, params, xd)
            idx, valid = select(arch, fl, acts, yd, k_sel)
            own = idx[valid]
            if follow is None:
                rows = np.array(acts[own], np.float32)
                if fault == "altered_row" and len(rows):
                    rows[0] = -rows[0]
                dec, _ = int8_roundtrip(rows)
                know.append(dec)
                know_y.append(y[own])
            else:
                f = follow[t]
                fj = f.cohort.index(cid) if cid in f.cohort else None
                if fj is None:
                    chosen = own
                else:
                    flat = acts.reshape(acts.shape[0], -1).astype(jnp.float32)
                    fdec = jnp.asarray(f.knowledge[fj], jnp.float32)
                    chosen = np.asarray(_nearest(
                        fdec.reshape(fdec.shape[0], -1), flat,
                        jnp.asarray(f.knowledge_labels[fj]), yd))
                rows = np.asarray(acts[chosen], np.float32)
                dec, scale = int8_roundtrip(rows)
                know.append(dec)
                know_y.append(y[chosen])
                picks.append((uid[own], uid[chosen], dec, scale))
            p_new, ls = local_update(arch, params, xd, yd, k_loc,
                                     half=fault == "half_batch",
                                     **_fl_args(fl))
            losses.append(float(np.asarray(ls, np.float32).mean()))
            cparams.append(p_new)
        kacts = jnp.asarray(np.concatenate(know, 0))
        kys = jnp.asarray(np.concatenate(know_y, 0))
        if kacts.shape[0]:
            up = meta_train(arch, upper0, kacts, kys, k_server,
                            **_meta_args(fl))
        else:
            up = upper0
        w = 1.0 / len(cparams)
        if fault != "unchanged":
            params = jax.tree.map(lambda *xs: sum(w * a for a in xs),
                                  *cparams)
        out.append(Round(cohort, losses, know, know_y, to_host(params),
                         to_host(up), picks))
    return out


# --------------------------------------------------------------------------
# the numbers compared
# --------------------------------------------------------------------------
def leaf_gap(prog, ref, base) -> float:
    """Worst leaf of | ||prog - base|| - ||ref - base|| | over the larger of
    the reference leaf's ||ref - base|| and the median leaf's. Leaves the
    reference moves by under a thousandth of the median leaf are left out."""
    pl = [np.asarray(a, np.float64) for a in jax.tree.leaves(prog)]
    rl = [np.asarray(a, np.float64) for a in jax.tree.leaves(ref)]
    bl = [np.asarray(a, np.float64) for a in jax.tree.leaves(base)]
    if not (len(pl) == len(rl) == len(bl)):
        return float("inf")
    pn = np.array([np.linalg.norm(p - b) for p, b in zip(pl, bl)])
    rn = np.array([np.linalg.norm(r - b) for r, b in zip(rl, bl)])
    med = float(np.median(rn))
    keep = rn >= 1e-3 * med
    if not keep.any() or med == 0:
        return float("inf")
    gaps = np.abs(pn - rn)[keep] / np.maximum(rn[keep], med)
    return float(gaps.max()) if np.all(np.isfinite(pn)) else float("inf")


def compare(prog: List[Round], ref: List[Round], w0) -> Dict[str, float]:
    """Numbers of the run under test against the reference that followed
    it. ``w0`` is the weights both started from (host arrays)."""
    r = min(len(prog), len(ref))
    if r == 0:
        return {"rounds_checked": 0.0}
    cohort_mismatch = sum(p.cohort != q.cohort for p, q in zip(prog, ref))
    missed = total = 0
    kgap = loss_gap = meta_gap = 0.0
    for p, q in zip(prog[:r], ref[:r]):
        for own_uid, chosen_uid, dec_ref, scale in q.picks:
            total += len(own_uid)
            missed += len(set(own_uid.tolist())
                          - set(chosen_uid.tolist()))
        for j, cid in enumerate(q.cohort):
            if cid not in p.cohort or j >= len(q.picks):
                continue
            pj = p.cohort.index(cid)
            dec_p = np.asarray(p.knowledge[pj], np.float32)
            _, _, dec_ref, scale = q.picks[j]
            if dec_p.shape != dec_ref.shape:
                kgap = float("inf")
            elif dec_p.size:
                kgap = max(kgap, float(np.abs(dec_p - dec_ref).max() / scale))
            lr_, lp_ = q.losses[j], p.losses[pj]
            loss_gap = max(loss_gap, abs(lp_ - lr_) / max(abs(lr_), 1e-12)
                           if np.isfinite(lp_) else float("inf"))
        meta_gap = max(meta_gap, leaf_gap(p.upper, q.upper,
                                          _upper_of(w0, q.upper)))
    return {
        "rounds_checked": float(r),
        "cohort_mismatch": float(cohort_mismatch),
        "select_miss": missed / max(total, 1),
        "knowledge_gap_levels": kgap,
        "loss_gap": loss_gap,
        "first_update_gap": leaf_gap(prog[0].global_params,
                                     ref[0].global_params, w0),
        "change_gap": leaf_gap(prog[r - 1].global_params,
                               ref[r - 1].global_params, w0),
        "meta_change_gap": meta_gap,
    }


def _upper_of(w0, upper_tree):
    return {k: w0[k] for k in upper_tree}
