"""Plain reference of a StarHTL city fleet (the program's ``city`` preset):
``fleet_size`` Data Collectors, each drawing ``obs_per_dc`` observations
a window from the training pool, a learning round after each window, an
EMA of the window model into the global one.

A window:

* **Draws.** DC ``g`` draws ``randint(fold_in(fold_in(PRNGKey(seed), t),
  g), (K,), 0, n_train)`` rows: JAX's threefry, computed here on the
  host's CPU, is the same bits on every backend.
* **Base models.** Every DC trains the paper's linear SVM
  (``plain.svm_train``'s arithmetic, step for step, batched over blocks
  of DCs and run by JAX on the host's CPU, one block per core at a time:
  ``plain.svm_train`` itself takes about twenty minutes a window over
  2.5e5 DCs).
* **Centre.** The highest label entropy (``plain.entropy``, over each
  DC's class counts sorted, so DCs whose counts are a permutation of each
  other tie), the lowest DC id on a tie.
* **Source screen.** Every DC but the centre is scored by GreedyTL's
  first-step objective on the centre's observations: its base model's
  scores there, scaled to unit RMS, and the per-class biases form a
  (1 + C)-unknown ridge (0.1 on the source, 2.0 on the biases, + 1e-4),
  its Gram matrix inverted directly, whose leave-one-out squared error
  is the score. The
  16 lowest, the lower id first on a tie, are the pool.
* **Refine and update.** ``plain.greedytl`` at the centre over the pool;
  the global model becomes the refined one (first window) or
  ``(1 - eta) w + eta refined``; ``plain.confusion`` on the test set.
* **Near ties.** GreedyTL's greedy choices, its stop rule and its
  stage-2 gate, and the screen's last pool place, are decisions: where
  their two sides lie within ``TIE`` (1%) of each other, the program's
  rounding may take the other side, and the model then moves by tens of
  times what rounding moves it elsewhere. So one such tie a window is
  also taken the other way
  (``greedytl_outcomes``, ``window_pools``), and every combination over
  the windows is a path, under ``tied``, with its pools and chosen
  sources; the comparison reads the path that chose as the program did.
* **Energy.** 802.15.4 collection (1 tx + 1 rx per DC), then over the
  configuration's technology every ordered pair's entropy index, the
  centre's id to every DC and every DC's model to the centre, under the
  paper's relay rule with the lowest id as the access point (``ledger``).

Only the first ``WINDOWS`` windows (``bench/comparisons/city_model.py``)
are computed: the program's output at window t depends on windows up to
t alone.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

from bench.comparisons.city_model import WINDOWS
from bench.references import ledger, plain

CLASSES = 7
BLOCK = 8192               # DCs per batch of base training and screening
TIE = 0.01                 # a decision's two sides this close are a tie
OUTCOMES = 16              # outcomes kept a window, the nearest ties first


def _cpu():
    import jax

    return jax.devices("cpu")[0]


@functools.lru_cache(maxsize=None)
def _drawer(K: int, n_train: int):
    import jax

    @jax.jit
    def draw(key, t, gid):
        kt = jax.random.fold_in(key, t)
        keys = jax.vmap(lambda g: jax.random.fold_in(kt, g))(gid)
        return jax.vmap(
            lambda k: jax.random.randint(k, (K,), 0, n_train))(keys)
    return draw


def _rounding(precision: str):
    """``plain.NUMS[precision]``'s (q, d) on float32 JAX arrays: round to
    nearest even, kept in float32, by integer arithmetic on the bits
    (which the host's CPU runs several times faster than a conversion to
    float8 and back)."""
    import jax
    import jax.numpy as jnp

    def same(a):
        return a

    def keep(a, bits: int):
        """``a`` rounded to ``bits`` bits of mantissa."""
        drop = 23 - bits
        b = jax.lax.bitcast_convert_type(a, jnp.uint32)
        b = b + ((1 << (drop - 1)) - 1) + ((b >> drop) & 1)
        b = b & jnp.uint32(0xFFFFFFFF << drop & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(b, jnp.float32)

    def bf16(a):
        return keep(a, 7)

    def fp8(a):
        # e4m3: 3 bits of mantissa from 2**-6 up, steps of 2**-9 below,
        # saturating at its largest finite value
        a = jnp.clip(a, -plain.E4M3_MAX, plain.E4M3_MAX)
        return jnp.where(jnp.abs(a) < 2.0 ** -6,
                         jnp.round(a * 512.0) / 512.0, keep(a, 3))
    return {"tpu_default": (same, bf16), "float32": (same, same),
            "control": (bf16, fp8)}[precision]


@functools.lru_cache(maxsize=None)
def _trainer(precision: str, iters: int, lam: float = 1e-3, lr: float = 0.5):
    """``plain.svm_train`` for one block of DCs, in float32 on JAX: the
    same steps, each result rounded by ``q`` and each product's operands
    by ``d``. Returns (B, F+1, C)."""
    import jax
    import jax.numpy as jnp

    q, d = _rounding(precision)
    hi = jax.lax.Precision.HIGHEST
    rates = np.float32(lr * 0.5 * (1.0 + np.cos(np.pi * np.arange(iters)
                                                / iters)))

    @jax.jit
    def train(x, y, m):
        x = q(x)
        dx, dxt = d(x), d(jnp.swapaxes(x, 1, 2))
        ypm = 2.0 * jax.nn.one_hot(y, CLASSES) - 1.0
        share = q(m / jnp.maximum(1.0, m.sum(axis=1))[:, None])[:, :, None]
        rate = jnp.asarray(rates)

        def step(i, carry):
            W, b, vW, vb = carry
            s = q(q(jnp.einsum("bnf,bfc->bnc", dx, d(W), precision=hi))
                  + b[:, None, :])
            active = q(1.0 - q(ypm * s)) > 0.0
            dS = q(-ypm * active * share)
            gW = q(q(jnp.einsum("bfn,bnc->bfc", dxt, d(dS), precision=hi))
                   + q(2.0 * lam * W))
            gb = q(dS.sum(axis=1))
            vW = q(q(0.9 * vW) - q(rate[i] * gW))
            vb = q(q(0.9 * vb) - q(rate[i] * gb))
            return q(W + vW), q(b + vb), vW, vb

        B, _, F = x.shape
        W0 = jnp.zeros((B, F, CLASSES), jnp.float32)
        b0 = jnp.zeros((B, CLASSES), jnp.float32)
        W, b, _, _ = jax.lax.fori_loop(0, iters, step, (W0, b0, W0, b0))
        return jnp.concatenate([W, b[:, None, :]], axis=1)
    return train


def base_models(x: np.ndarray, y: np.ndarray, iters: int,
                precision: str) -> np.ndarray:
    """Every DC's base SVM, (L, F+1, C) float64 (of float32 values), in
    blocks of ``BLOCK`` DCs (the last padded with masked-out DCs), one
    block per host core at a time."""
    import jax

    train = _trainer(precision, iters)
    L, K, _ = x.shape
    out = np.empty((L, x.shape[2] + 1, CLASSES))
    B = min(BLOCK, L)

    def block(lo):
        n = min(B, L - lo)
        xb = np.zeros((B,) + x.shape[1:], np.float32)
        yb = np.zeros((B, K), np.int32)
        mb = np.zeros((B, K), np.float32)
        xb[:n], yb[:n], mb[:n] = x[lo:lo + n], y[lo:lo + n], 1.0
        w = train(*(jax.device_put(a, _cpu()) for a in (xb, yb, mb)))
        out[lo:lo + n] = np.asarray(w)[:n]

    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        list(pool.map(block, range(0, L, B)))
    return out


def elect(y: np.ndarray) -> int:
    """The DC of highest label entropy, the lowest id on a tie; entropies
    over sorted counts, computed once per distinct multiset."""
    counts = np.zeros((len(y), CLASSES), np.int64)
    np.add.at(counts, (np.repeat(np.arange(len(y)), y.shape[1]),
                       y.reshape(-1)), 1)
    kinds, which = np.unique(np.sort(counts, axis=1), axis=0,
                             return_inverse=True)
    ent = np.array([plain.entropy(np.repeat(np.arange(CLASSES), k), CLASSES)
                    for k in kinds])[which.reshape(-1)]
    return int(np.flatnonzero(ent == ent.max())[0])


def screen_scores(base: np.ndarray, cx: np.ndarray, cy: np.ndarray,
                  num: plain.Num, lam_src: float = 0.1,
                  lam_bias: float = 2.0) -> np.ndarray:
    """GreedyTL's first-step objective of every DC's base model as the one
    source beside the C biases, on the centre's data. Each DC's ridge has
    1 + C unknowns (its column a = the scaled scores, then the biases): G
    = A^T A + diag(lambda) is written out and inverted, and the stacked
    row r of class c has leverage h_r = a_r^2 G^-1_00 + 2 a_r G^-1_0c +
    G^-1_cc and fit a_r v_0 + v_c. Returns (L,)."""
    q = num.q
    K, F = cx.shape
    C = CLASSES
    Y = 2.0 * plain.onehot(cy, C) - 1.0                       # (K, C)
    lam = np.diag(np.concatenate([[lam_src], np.full(C, lam_bias)]) + 1e-4)
    out = np.empty(len(base))
    for lo in range(0, len(base), BLOCK):
        w = base[lo:lo + BLOCK]
        B = len(w)
        H = q(num.ein("kf,lfc->lkc", cx, w[:, :F]) + w[:, F][:, None, :])
        s = q(np.sqrt(q(q((H ** 2).sum(axis=(1, 2))) / (K * C))) + 1e-6)
        a = q(H / s[:, None, None])                           # (B, K, C)
        G = np.zeros((B, 1 + C, 1 + C))
        G[:, 0, 0] = (a ** 2).sum(axis=(1, 2))
        G[:, 0, 1:] = G[:, 1:, 0] = a.sum(axis=1)
        G[:, 1:, 1:] = K * np.eye(C)
        G += lam
        g = np.concatenate([(a * Y).sum(axis=(1, 2))[:, None],
                            np.broadcast_to(Y.sum(axis=0), (B, C))], axis=1)
        Gi = np.linalg.inv(G)
        v = np.einsum("bij,bj->bi", Gi, g)
        h = (a ** 2 * Gi[:, 0, 0][:, None, None]
             + 2.0 * a * Gi[:, 0, 1:][:, None, :]
             + np.diagonal(Gi[:, 1:, 1:], axis1=1, axis2=2)[:, None, :])
        resid = a * v[:, 0][:, None, None] + v[:, None, 1:] - Y
        out[lo:lo + B] = q(
            ((resid / np.maximum(1.0 - h, 0.1)) ** 2).sum(axis=(1, 2)))
    return out


def _margin(a: float, b: float) -> float:
    """How far apart two numbers a decision compares lie, relative to the
    larger: a tie where it is at most ``TIE``."""
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


def greedytl_outcomes(x: np.ndarray, y: np.ndarray, src_w: np.ndarray,
                      src_mask: np.ndarray, num: plain.Num, *,
                      ties: bool = True, lam_src: float = 0.1,
                      lam_x: float = 10.0, lam_bias: float = 2.0,
                      k_max: int = 16) -> list:
    """``plain.greedytl`` at the centre (every observation kept), with its
    near ties opened: what it returns when one decision whose two sides
    lie within ``TIE`` of each other (``_margin``) is taken the other way.
    A decision is a greedy step's choice (the least trial objective
    against another within ``TIE`` of it), its stop rule (the least trial
    against the active set's objective) and the stage-2 gate (the
    correction's LOO sum against the residual's).
    The first outcome takes every decision as ``plain.greedytl`` does,
    and its model is that function's to the bit; with no tie, or with
    ``ties`` false, it is the only one; the others follow by their tie's
    margin, the nearest first. Returns a list of (margin, model (F+1, C),
    the chosen sources' indices sorted)."""
    q = num.q
    C = CLASSES
    n, F = x.shape
    M = src_w.shape[0]
    xm = q(x)
    Yoh = 2.0 * plain.onehot(y, C) - 1.0                      # (n, C)
    H = q(num.ein("nf,mfc->mnc", xm, src_w[:, :F]) + src_w[:, F][:, None, :])
    s = q(np.sqrt(q(q((H ** 2).sum(axis=(1, 2))) / (n * C))) + 1e-6)
    Hn = q(H / s[:, None, None])
    R = n * C
    rmask = np.ones(R)
    A = np.concatenate([Hn.transpose(1, 2, 0).reshape(R, M),
                        np.tile(np.eye(C), (n, 1))], axis=1)
    yr = Yoh.reshape(R)
    lam = np.concatenate([np.full(M, lam_src), np.full(C, lam_bias)]) + 1e-4
    G = num.mm(A.T, A)
    g = num.mm(A.T, yr)

    def objective(sets):
        cols = np.asarray(sets)
        Gs = q(G[cols[:, :, None], cols[:, None, :]]
               + np.einsum("tk,kj->tkj", lam[cols], np.eye(cols.shape[1])))
        return plain._loo(Gs, np.transpose(A[:, cols], (1, 0, 2)), g[cols],
                          yr, rmask, num)

    G2 = q(num.mm(xm.T, xm) + np.diag(np.full(F, lam_x + 1e-4)))
    out = []

    def finish(active, chosen, opened):
        _, v = objective([active])
        coef = np.zeros(M + C)
        coef[active] = v[0]
        bias = coef[M:]
        w = num.ein("m,mfc->fc", q(coef[:M] / s), src_w)
        w[F] = q(w[F] + bias)
        fit = q(num.ein("m,mnc->nc", coef[:M], Hn) + bias[None, :])
        resid = q(Yoh - fit)
        loo_x, vx = plain._stage2(G2, xm, resid, np.ones(n), num)
        a, b = q(loo_x.sum()), q((resid ** 2).sum())
        sides = [(a < b, opened)]
        if opened is None and _margin(a, b) <= TIE:      # the stage-2 gate
            sides.append((not a < b, _margin(a, b)))
        for keep, tie in sides:
            wk = w.copy()
            if keep:
                wk[:F] = q(w[:F] + vx.T)
            out.append((0.0 if tie is None else tie, wk, sorted(chosen)))

    def walk(active, chosen, best, opened):
        """The greedy steps from ``active``: each decision as
        ``plain.greedytl`` takes it, then, while no tie is ``opened`` on
        this path (else the margin of the one that is), its other sides
        within ``TIE``."""
        cands = [j for j in range(M) if src_mask[j] > 0 and j not in chosen]
        if len(chosen) >= min(k_max, M) or not cands:
            return finish(active, chosen, opened)
        objs, _ = objective([active + [j] for j in cands])
        i = int(np.argmin(objs))
        go = objs[i] < best

        def step(j, opened):
            walk(active + [cands[j]], chosen + [cands[j]], objs[j], opened)
        step(i, opened) if go else finish(active, chosen, opened)
        if opened is not None:
            return
        tie = _margin(objs[i], best)
        if tie <= TIE:                            # the stop rule
            finish(active, chosen, tie) if go else step(i, tie)
        for j in range(len(cands)):               # the choice
            tie = _margin(objs[j], objs[i])
            if go and j != i and objs[j] < best and tie <= TIE:
                step(j, tie)

    walk(list(range(M, M + C)), [], objective([list(range(M, M + C))])[0][0],
         None if ties else 0.0)
    return out[:1] + sorted(out[1:], key=lambda o: o[0])


def window_pools(order: np.ndarray, score: np.ndarray, n: int) -> list:
    """The screen's pool, the first ``n`` of ``order``, then the pools in
    which one DC ranked after it whose score lies within ``TIE`` of the
    pool's last takes that last place, the nearest first (at most
    ``OUTCOMES - 1``). Returns a list of (margin, pool)."""
    pools = [(0.0, order[:n])]
    last = score[order[n - 1]] if n else np.inf
    for g in order[n:n + OUTCOMES - 1]:
        if not (np.isfinite(last) and np.isfinite(score[g])
                and _margin(score[g], last) <= TIE):
            break
        pools.append((_margin(score[g], last),
                      np.concatenate([order[:n - 1], [g]])))
    return pools


def city_energy(tech: str, n: int, K: int, centre_is_ap: bool
                ) -> Tuple[float, float]:
    """One window's (collection, learning) mJ for ``n`` DCs: every role
    pair's unicast counts times how many such pairs there are."""
    collection = ledger.transfer_mj("802.15.4", K * ledger.OBS_BYTES, n, n)

    def charge(nbytes, pairs):
        tx = rx = 0
        for mult, src_ap, dst_ap in pairs:
            a, b = ledger.unicast_counts(tech, False, False, src_ap, dst_ap)
            tx += mult * a
            rx += mult * b
        return ledger.transfer_mj(tech, nbytes, tx, rx)

    index = charge(ledger.INDEX_BYTES, [(n - 1, True, False),
                                        (n - 1, False, True),
                                        ((n - 1) * (n - 2), False, False)])
    if centre_is_ap:
        centre_id = charge(ledger.INDEX_BYTES, [(n - 1, True, False)])
        gather = charge(ledger.MODEL_BYTES, [(n - 1, False, True)])
    else:
        centre_id = charge(ledger.INDEX_BYTES, [(1, False, True),
                                                (n - 2, False, False)])
        gather = charge(ledger.MODEL_BYTES, [(1, True, False),
                                             (n - 2, False, False)])
    return collection, index + centre_id + gather


def answer(scn: dict, data, precision: str = "tpu_default") -> dict:
    """The first ``WINDOWS`` windows' global models, source pools,
    centres and F1, and their energy totals; ``precision`` names a
    :data:`plain.NUMS` entry (the contract's control is ``"control"``)."""
    import jax

    num = plain.NUMS[precision]
    q = num.q
    for key, neutral in (("drift", "none"), ("byz_frac", 0.0),
                         ("battery_mj", None), ("robust_agg", "mean"),
                         ("p_edge", 0.0)):
        if scn.get(key) != neutral:
            raise ValueError(f"the city reference covers {key}={neutral!r}")
    if scn["algo"] != "star" or not scn.get("fleet_size"):
        raise ValueError("the city reference covers StarHTL city fleets")
    L, K, W = scn["fleet_size"], scn["obs_per_dc"], scn["windows"]
    xtr = np.asarray(data.x_train, np.float32)
    ytr = np.asarray(data.y_train, np.int32)
    xte = np.asarray(data.x_test, np.float32).astype(np.float64)
    yte = np.asarray(data.y_test, np.int64)
    eta = np.float32(scn["global_update_rate"])
    with jax.default_device(_cpu()):
        key = jax.random.PRNGKey(scn["seed"])
        gid = jax.numpy.arange(L, dtype=jax.numpy.int32)
        draw = _drawer(K, len(ytr))
        out = {"centre": [], "collection_mj": 0.0, "learning_mj": 0.0}
        paths = [[]]        # (global model, pool, chosen) a window, per path

        def ema(path, model):
            if not path:
                return model
            return q(q((np.float32(1.0) - eta) * path[-1][0]) + q(eta * model))
        for t in range(min(W, WINDOWS)):
            idx = np.asarray(draw(key, np.int32(t), gid))
            x, y = xtr[idx], ytr[idx]                  # (L, K, F), (L, K)
            base = base_models(x, y, scn["train_iters"], precision)
            c = elect(y)
            cx, cy = x[c].astype(np.float64), y[c]
            score = screen_scores(base, cx, cy, num)
            score[c] = np.inf
            order = np.lexsort((np.arange(L), score))
            n = min(plain.M_CAP, L - 1)
            found = []                  # (margin, model, pool, chosen)
            for tie, pool in window_pools(order, score, n):
                src = np.zeros((plain.M_CAP,) + base.shape[1:])
                src[:n] = base[pool]
                mask = (np.arange(plain.M_CAP) < n).astype(float)
                gids = [int(g) for g in pool] + [-1] * (plain.M_CAP - n)
                for margin, model, chosen in greedytl_outcomes(
                        cx, cy, src, mask, num, ties=not found):
                    found.append((max(tie, margin), model, gids,
                                  sorted(int(pool[j]) for j in chosen)))
            outcomes = []
            for _, model, gids, chosen in found[:1] + sorted(
                    found[1:], key=lambda o: o[0]):
                if len(outcomes) < OUTCOMES and not any(
                        chosen == c and sorted(gids) == sorted(g)
                        and np.array_equal(model, m)
                        for m, g, c in outcomes):
                    outcomes.append((model, gids, chosen))
            paths = [p + [(ema(p, model), gids, chosen)] for p in paths
                     for model, gids, chosen in outcomes]
            out["centre"].append(c)
            col, learn = city_energy(scn["tech"], L, K, centre_is_ap=c == 0)
            out["collection_mj"] += col
            out["learning_mj"] += learn
    walks = [{"models": [w.tolist() for w, _, _ in p],
              "pool": [gids for _, gids, _ in p],
              "selected": [chosen for _, _, chosen in p],
              "f1_curve": [plain.f_measure(plain.confusion(
                  w, xte, yte, CLASSES, num)) for w, _, _ in p]}
             for p in paths]
    out.update(walks[0], tied=walks[1:])
    return out
