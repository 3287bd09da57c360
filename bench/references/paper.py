"""Plain reference of the paper's scenario (arXiv:2109.11386, Sec. 3-6):
``windows`` collection windows of ``obs_per_window`` observations, a
learning round after each, an EMA of the window model into the global one.

Host randomness follows one ``numpy.random.default_rng(seed)`` stream, in
the scenario's stated order: the stream's permutation of the training
pool; then per window a permutation splitting edge-server observations
(fraction ``p_edge``, NB-IoT) from mule observations, the Poisson(lambda)
mule count (at least 1) and the mules' allocation (Zipf(alpha) over the
mule ranks, or uniform); then, for the round, GreedyTL's per-class
subsamples (``n_subsample`` points a class, drawn without replacement
class by class) at every live DC (A2AHTL) or at the centre (StarHTL).

A round: the aggregation heuristic (DCs under 8 observations send their
data to the largest of them, which alone joins), then every live DC
trains its base SVM (200 iterations); the source pool is the first 16
live DCs' base models plus the previous global model while there is room.
A2AHTL refines at every live DC and averages; StarHTL elects the
highest-entropy DC (the first on a tie) and refines there. A window with
one live DC averages its base model with the global one. The global model
starts at zero; an empty window keeps it.

Energy: 802.15.4 sensor->mule collection (1 tx + 1 rx), NB-IoT
sensor->edge server (tx only), and the round's unicasts (aggregation,
model or index exchange over every ordered pair, centre-id broadcast,
gather to the centre or, for A2AHTL, to the access point, the largest
mule) under the transport's relay rule (:mod:`ledger`).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from bench.references import ledger, plain

CLASSES = 7
AGG_THRESHOLD = int(np.ceil(2 * ledger.MODEL_BYTES / ledger.OBS_BYTES))


class DC:
    def __init__(self, name: str, x, y, es: bool = False):
        self.name, self.x, self.y, self.es = name, x, y, es

    @property
    def n(self) -> int:
        return len(self.y)


class Energy:
    def __init__(self):
        self.collection = 0.0
        self.learning = 0.0

    def add(self, purpose: str, tech: str, nbytes: float, n_tx: int,
            n_rx: int) -> None:
        mj = ledger.transfer_mj(tech, nbytes, n_tx, n_rx)
        if purpose == "collection":
            self.collection += mj
        else:
            self.learning += mj

    def unicast(self, tech: str, src: DC, dst: DC, ap: Optional[str],
                nbytes: float) -> None:
        n_tx, n_rx = ledger.unicast_counts(tech, src.es, dst.es,
                                           src.name == ap, dst.name == ap)
        self.add("learning", tech, nbytes, n_tx, n_rx)

    def all_pairs(self, tech, dcs, ap, nbytes):
        for s in dcs:
            for d in dcs:
                if s.name != d.name:
                    self.unicast(tech, s, d, ap, nbytes)

    def to_all(self, tech, src, dcs, ap, nbytes):
        for d in dcs:
            if d.name != src.name:
                self.unicast(tech, src, d, ap, nbytes)

    def from_all(self, tech, dst, dcs, ap, nbytes):
        for s in dcs:
            if s.name != dst.name:
                self.unicast(tech, s, dst, ap, nbytes)


def largest_mule(dcs: List[DC]) -> Optional[str]:
    mules = [d for d in dcs if not d.es]
    return max(mules, key=lambda d: d.n).name if mules else None


def collect(scn, rng, wx, wy, energy: Energy) -> List[DC]:
    opw = scn["obs_per_window"]
    n_edge = int(round(scn["p_edge"] * opw))
    idx = rng.permutation(opw)
    edge, mule = idx[:n_edge], idx[n_edge:]
    n_mules = max(1, rng.poisson(scn["lam_poisson"]))
    uniform = scn["collection"] == "uniform" or (
        scn["uniform"] and scn["collection"] == "poisson_zipf")
    if uniform:
        assign = rng.integers(0, n_mules, size=len(mule))
    elif scn["collection"] == "poisson_zipf":
        p = np.arange(1, n_mules + 1, dtype=np.float64) ** (
            -scn["zipf_alpha"])
        assign = rng.choice(n_mules, size=len(mule), p=p / p.sum())
    else:
        raise ValueError(f"the paper reference covers the Poisson "
                         f"collection policies, not {scn['collection']!r}")
    dcs = []
    for m in range(n_mules):
        sel = mule[assign == m]
        if len(sel):
            energy.add("collection", "802.15.4",
                       len(sel) * ledger.OBS_BYTES, 1, 1)
            dcs.append(DC(f"SM{m + 1}", wx[sel], wy[sel]))
    if n_edge > 0:
        energy.add("collection", "nbiot", n_edge * ledger.OBS_BYTES, 1, 0)
        if scn["include_es_in_learning"]:
            dcs.append(DC("ES", wx[edge], wy[edge], es=True))
    return dcs


def aggregate(dcs: List[DC], energy: Energy, tech: str) -> List[DC]:
    small = [d for d in dcs if not d.es and d.n < AGG_THRESHOLD]
    big = [d for d in dcs if d.es or d.n >= AGG_THRESHOLD]
    if len(small) <= 1:
        return dcs
    ap = largest_mule(dcs)
    small = sorted(small, key=lambda d: -d.n)
    sink = small[0]
    for d in small[1:]:
        energy.unicast(tech, d, sink, ap, d.n * ledger.OBS_BYTES)
    return big + [DC(sink.name, np.concatenate([d.x for d in small]),
                     np.concatenate([d.y for d in small]))]


def subsample(dc: DC, per_class: Optional[int], rng) -> DC:
    if per_class is None or dc.n == 0:
        return dc
    keep = []
    for c in range(CLASSES):
        idx = np.where(dc.y == c)[0]
        if len(idx) > per_class:
            idx = rng.choice(idx, per_class, replace=False)
        keep.append(idx)
    keep = np.concatenate(keep)
    return DC(dc.name, dc.x[keep], dc.y[keep], dc.es)


def _base_models(dcs: List[DC], iters: int, num: plain.Num
                 ) -> List[np.ndarray]:
    """Every DC's base SVM, batched by padded size (padding rows are
    masked out and add exact zeros)."""
    out: List[Optional[np.ndarray]] = [None] * len(dcs)
    for size in sorted({_bucket(d.n) for d in dcs}):
        group = [i for i, d in enumerate(dcs) if _bucket(d.n) == size]
        x = np.zeros((len(group), size, dcs[group[0]].x.shape[1]))
        y = np.zeros((len(group), size), np.int64)
        m = np.zeros((len(group), size))
        for j, i in enumerate(group):
            n = dcs[i].n
            x[j, :n], y[j, :n], m[j, :n] = dcs[i].x, dcs[i].y, 1.0
        w = plain.svm_train(x, y, m, classes=CLASSES, iters=iters, num=num)
        for j, i in enumerate(group):
            out[i] = w[j]
    return out


def _bucket(n: int) -> int:
    return 16 if n <= 16 else 64 if n <= 64 else 1 << (n - 1).bit_length()


def _refine(dc: DC, src, mask, num: plain.Num) -> np.ndarray:
    return plain.greedytl(dc.x, dc.y, np.ones(dc.n), src, mask,
                          classes=CLASSES, num=num)


def answer(scn: dict, data, precision: str = "tpu_default") -> dict:
    """The scenario's F1 curve and energy totals; ``precision`` names a
    :data:`plain.NUMS` entry (the contract's control is ``"control"``)."""
    num = plain.NUMS[precision]
    q = num.q
    for key, neutral in (("drift", "none"), ("byz_frac", 0.0),
                         ("battery_mj", None), ("robust_agg", "mean"),
                         ("fleet_size", None)):
        if scn.get(key) != neutral:
            raise ValueError(f"the paper reference covers {key}={neutral!r}")
    if scn["algo"] not in ("a2a", "star"):
        raise ValueError(f"the paper reference covers HTL, not "
                         f"{scn['algo']!r}")
    W, opw, tech = scn["windows"], scn["obs_per_window"], scn["tech"]
    eta = scn["global_update_rate"]
    rng = np.random.default_rng(scn["seed"])
    order = rng.permutation(len(data.y_train))[:W * opw]
    sx = np.asarray(data.x_train[order], np.float32).astype(np.float64)
    sy = np.asarray(data.y_train[order], np.int64)
    xte = np.asarray(data.x_test, np.float32).astype(np.float64)
    yte = np.asarray(data.y_test, np.int64)
    energy = Energy()

    # host work first (it draws from the rng in the scenario's order and
    # needs no model), then every base SVM at once, then the rounds
    plans = [_plan(scn, rng, sx[t * opw:(t + 1) * opw],
                   sy[t * opw:(t + 1) * opw], energy) for t in range(W)]
    every = [d for live, _ in plans for d in live]
    base = iter(_base_models(every, scn["train_iters"], num))
    w: Optional[np.ndarray] = None
    curve = []
    for t, (live, refine) in enumerate(plans):
        models = [next(base) for _ in live]
        new = None
        if len(live) == 1:
            new = models[0] if w is None else q(0.5 * q(models[0] + w))
        elif live:
            sources = models[:plain.M_CAP]
            if w is not None and len(live) < plain.M_CAP:
                sources = sources + [w]
            src = np.zeros((plain.M_CAP,) + models[0].shape)
            src[:len(sources)] = sources
            mask = (np.arange(plain.M_CAP) < len(sources)).astype(float)
            refined = [_refine(d, src, mask, num) for d in refine]
            new = q(np.sum(refined, axis=0) / len(refined))
        if new is not None:
            w = new if w is None else q(q((1.0 - eta) * w) + q(eta * new))
        if (t + 1) % scn["eval_every"] == 0:
            model = w if w is not None else np.zeros(
                (sx.shape[1] + 1, CLASSES))
            curve.append(plain.f_measure(plain.confusion(model, xte, yte,
                                                         CLASSES, num)))
    return {"f1_curve": curve, "collection_mj": energy.collection,
            "learning_mj": energy.learning}


def _plan(scn, rng, wx, wy, energy: Energy) -> Tuple[List[DC], List[DC]]:
    """One window's host work: collection, aggregation, the round's
    messages and GreedyTL's subsamples. Returns (live DCs, the DCs that
    refine: every live one for A2AHTL, the centre for StarHTL)."""
    tech, n_sub = scn["tech"], scn["n_subsample"]
    dcs = collect(scn, rng, wx, wy, energy)
    if scn["aggregate"]:
        dcs = aggregate(dcs, energy, tech)
    live = [d for d in dcs if d.n > 0]
    if len(live) < 2:
        return live, []
    ap = largest_mule(live)
    if scn["algo"] == "a2a":
        energy.all_pairs(tech, live, ap, ledger.MODEL_BYTES)
        refine = [subsample(d, n_sub, rng) for d in live]
        centre = next((d for d in live if d.name == ap), live[0])
        energy.from_all(tech, centre, live, ap, ledger.MODEL_BYTES)
    else:
        energy.all_pairs(tech, live, ap, ledger.INDEX_BYTES)
        ent = [plain.entropy(d.y, CLASSES) for d in live]
        centre = live[int(np.argmax(ent))]
        energy.to_all(tech, centre, live, ap, ledger.INDEX_BYTES)
        energy.from_all(tech, centre, live, ap, ledger.MODEL_BYTES)
        refine = [subsample(centre, n_sub, rng)]
    return live, refine
