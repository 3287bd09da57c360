"""Million-DC fleet engine: scan-over-windows + shard_map'd DC axis.

Two engines live here, both collapsing a whole scenario into O(1) jitted
dispatches (the fleet engine of :mod:`repro.core.fleet` still drives each
window from Python and round-trips fleet state host<->device per window):

**Paper-scale scan engine** (``engine="scan"``, :func:`run_scenario_scan`).
A host-side *planner* replays the scenario's host work exactly as the fleet
engine would — same rng consumption order (collection, then GreedyTL
subsampling), same per-pair ledger events in the same order, same AP/center
election and single-DC early exits — but instead of dispatching per window
it packs every window's fleet into ``(W, ...)`` arrays, the observations
as one compact row table that the program pads into blocks on the device.
One jitted ``lax.scan`` over windows then fuses base training -> GreedyTL
refine -> EMA into a single carried fleet state ``(w_global, has_global)``,
and evaluation is *streamed*: each window emits an integer confusion matrix
(exact in f32 — counts < 2^24), from which the host recovers the paper's
F1 bitwise (:func:`repro.core.metrics.f_measure_from_confusion`). Ledgers
are host-replayed and therefore exactly equal; F1 parity is at prediction
level (weights agree to float roundoff; the scan-vs-fleet SweepResult JSON
gate in scripts/scan_parity.py pins equality on the smoke and
transport_grid presets).

**City engine** (``engine="scan"`` + ``fleet_size``, :func:`run_city`).
The 10^5-DC smart-city scenario the paper motivates but never runs: a
StarHTL fleet of ``fleet_size`` DCs, each drawing ``obs_per_dc``
observations per window *on device* (per-DC ``fold_in`` PRNG keys, so the
draw is shard-count invariant), sharded over the DC mesh axis
(:func:`repro.sharding.partitioning.fleet_mesh`) with
``jax.shard_map``. No per-DC Python objects exist; fleet
state stays device-resident across the whole scan; cross-shard reductions
are exact (one-hot ``psum`` for the source pool and center dataset,
lexicographic max for the entropy election), so shard counts 1..8 produce
bitwise-identical results (tests/test_cityscan.py). Energy is charged
analytically: per-role-pair transfer counts from the transport layer times
combinatorial multiplicities — O(1) ledger events per window instead of
the loop/fleet engines' O(L^2). Memory is flat in both window count (scan
reuses one window's buffers) and — per DC — fleet size.

Both engines inline ``_greedytl`` into their jitted scan bodies, so the
greedy refine they compile is the incremental factor carry of DESIGN.md
§11 (fixed-shape padded ``Ut``/``Cc``/``z`` through the inner
``while_loop``; the carry is what keeps the whole-scenario program a
single compilation unit at any greedy depth).

The DC axis is bucket-padded with the PR-1/2 machinery
(:func:`repro.core.fleet.fleet_cap`, multiples of 32) so Poisson fleet
sizes never recompile, and shard counts divide every padded capacity.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import htl
from repro.core.dispatch import count_dispatch, span
from repro.core.energy import (INDEX_BYTES, Ledger, MODEL_BYTES, OBS_BYTES)
from repro.core.fleet import fleet_cap
from repro.core.greedytl import _greedytl
from repro.core.htl import DC, M_CAP, apply_aggregation_heuristic
from repro.core.metrics import f_measure_from_confusion
from repro.core.svm import _train_svm, sample_cap
from repro.core.topology import Node, Topology, fleet_nodes, get_transport
from repro.data.synthetic_covtype import Dataset, NUM_CLASSES
from repro.sharding.partitioning import FLEET_AXIS, dc_shards, fleet_mesh


# ---------------------------------------------------------------------------
# shared eval plumbing: device test arrays come from the scenario module's
# EvalCache (lazy import; scenario.py imports this module lazily too)
# ---------------------------------------------------------------------------

def _eval_arrays(data: Dataset):
    from repro.core.scenario import _eval_cache
    x_test = _eval_cache.array(
        data, "test", lambda d: jnp.asarray(d.x_test.astype(np.float32)))
    y_oh = _eval_cache.array(
        data, "test_onehot",
        lambda d: jnp.asarray(np.eye(NUM_CLASSES, dtype=np.float32)
                              [np.asarray(d.y_test, np.int64)]))
    return x_test, y_oh


def _train_arrays(data: Dataset):
    from repro.core.scenario import _eval_cache
    xtr = _eval_cache.array(
        data, "train_x", lambda d: jnp.asarray(d.x_train.astype(np.float32)))
    ytr = _eval_cache.array(
        data, "train_y", lambda d: jnp.asarray(d.y_train.astype(np.int32)))
    return xtr, ytr


def _f1_curve(cms: np.ndarray, eval_every: int) -> List[float]:
    """Streamed F1: per-window integer confusion counts -> paper F1."""
    out = []
    for t in range(cms.shape[0]):
        if (t + 1) % eval_every == 0:
            out.append(f_measure_from_confusion(cms[t].astype(np.int64)))
    return out


def _window_cm(w, x_test, y_oh, num_classes: int):
    """One window's streamed eval: confusion counts, exact in f32."""
    scores = x_test @ w[:-1] + w[-1]
    pred = jax.nn.one_hot(jnp.argmax(scores, axis=-1), num_classes)
    return y_oh.T @ pred


# ---------------------------------------------------------------------------
# paper-scale scan engine: host-replay planner
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _WindowPlan:
    live: List[DC]                 # non-empty DCs, fleet-engine order
    refine: List[DC]               # a2a: per-DC subsampled; star: [center]
    n_pool: int = 0                # base models entering the source pool
    prev_slot: int = -1            # pool slot of the previous global model
    single: bool = False


def _plan_scenario(cfg, data: Dataset) -> Tuple[List[_WindowPlan], Ledger]:
    """Replay every window's host-side work exactly as run_scenario with the
    fleet engine would: identical rng consumption order (collection policy,
    then per-DC subsampling), identical ledger events in identical order
    (collection; then per-pair m0 exchange / entropy index / center id /
    gather events through the same Topology patterns), identical AP/center
    election and single-DC early exits. Only the jitted numerics are left
    for the scan program."""
    from repro.core.scenario import ChurnBook, build_stream, collect_window

    with span("plan", windows=cfg.windows) as sp:
        rng = np.random.default_rng(cfg.seed)
        ledger = Ledger()
        # realism axis rides along for free: the (possibly drifted) stream
        # comes from the shared build_stream, churn/byzantine faults happen
        # inside the shared collect_window — a churned-away window becomes
        # an empty plan, masked by the scan program's ``learn`` flag
        # (alive-state masking: jitted shapes never change, dead fleets are
        # zero rows)
        sx, sy = build_stream(cfg, data, rng)
        churn = (None if cfg.battery_mj is None
                 else ChurnBook(cfg.battery_mj))

        plans: List[_WindowPlan] = []
        prev_exists = False
        live_dcs = 0
        for t in range(cfg.windows):
            s = slice(t * cfg.obs_per_window, (t + 1) * cfg.obs_per_window)
            dcs = collect_window(cfg, rng, sx[s], sy[s], ledger,
                                 window=t, churn=churn)
            if cfg.aggregate:
                dcs = apply_aggregation_heuristic(dcs, ledger, cfg.tech)
            live = [d for d in dcs if d.n > 0]
            live_dcs += len(live)
            if not live:
                plans.append(_WindowPlan([], []))
                continue
            if len(live) == 1:
                plans.append(_WindowPlan(live, [], single=True))
                prev_exists = True
                continue
            ap = htl._ap_name(live)
            topo = Topology(ledger, cfg.tech, fleet_nodes(live, ap))
            if cfg.algo == "a2a":
                topo.exchange_all(MODEL_BYTES, what="m0 exchange")
                refine = [htl._subsample(d, cfg.n_subsample, NUM_CLASSES,
                                         rng) for d in live]
                center = next((d for d in live if d.name == ap), live[0])
                topo.gather(topo.node(center.name), MODEL_BYTES,
                            what="m1 gather")
            else:
                topo.exchange_all(INDEX_BYTES, what="entropy index")
                c_idx = int(np.argmax([htl.label_entropy(d.y, NUM_CLASSES)
                                       for d in live]))
                center = live[c_idx]
                topo.broadcast(topo.node(center.name), INDEX_BYTES,
                               what="center id")
                topo.gather(topo.node(center.name), MODEL_BYTES,
                            what="m0 to center")
                refine = [htl._subsample(center, cfg.n_subsample,
                                         NUM_CLASSES, rng)]
            n_pool = min(len(live), M_CAP)
            prev_slot = (len(live) if (prev_exists and len(live) < M_CAP)
                         else -1)
            plans.append(_WindowPlan(live, refine, n_pool, prev_slot))
            prev_exists = True
        sp.set_metadata(dcs=live_dcs, events=len(ledger.events))
    return plans, ledger


def _row_bucket(n: int) -> int:
    """Rows of the uploaded table for ``n`` placed observations: a power of
    two from 1024, so a preset's scenarios share programs."""
    return max(1024, 1 << max(n - 1, 0).bit_length())


def _slot_counts(shape, per_window: np.ndarray, n: np.ndarray, cap: int
                 ) -> np.ndarray:
    """Rows placed in each slot of a ``(W, L)`` (or ``(W,)``) block:
    window ``t``'s ``per_window[t]`` DCs, of ``n`` observations each in
    table order, fill its first slots, ``min(n, cap)`` rows apiece."""
    c = np.zeros(shape, np.int32)
    win = np.repeat(np.arange(len(per_window)), per_window)
    first = np.repeat(np.cumsum(per_window) - per_window, per_window)
    c.reshape(len(per_window), -1)[win, np.arange(len(win)) - first] = \
        np.minimum(n, cap)
    return c


def _placement(counts: np.ndarray, cap: int, first: int):
    """Where a block's rows sit: the table row of every slot's first
    observation (``first`` plus the running sum of the counts before it,
    in slot order) and the flat index, in a ``(..., cap)`` block, of the
    slot each of the block's table rows fills, in table order."""
    c = counts.ravel()
    ends = np.cumsum(c)
    starts = ends - c
    slot_of_row = (np.repeat(np.arange(c.size) * cap - starts, c)
                   + np.arange(int(ends[-1])))
    return (starts + first).reshape(counts.shape).astype(np.int32), \
        slot_of_row


def _pack_plan(cfg, plans: List[_WindowPlan]) -> dict:
    """Second pass: lay every window onto one stable (W, ...) block layout
    — DC axis at the bucketed fleet capacity, samples at the max bucketed
    sample capacity over all windows — so one scan program serves every
    Poisson draw of the scenario.

    The observations go up once, compact: ``x_rows`` holds every live DC's
    first ``min(n, cap)`` rows and then the refine rows, in slot order,
    zero rows up to :func:`_row_bucket`; ``xb_start``/``xb_count`` and
    ``xr_start``/``xr_count`` place them, and the scan program builds the
    zero-padded ``xb``/``xr`` blocks from them on the device
    (:func:`_scan_inputs`). Labels and masks keep their padded blocks."""
    with span("pack") as sp:
        W = cfg.windows
        multi = np.array([bool(p.live) and not p.single for p in plans])
        lens = np.array([len(p.live) for p in plans])
        rlens = np.array([len(p.refine) for p in plans]) * multi
        base = [d for p in plans for d in p.live]          # table order
        refine = [d for p, m in zip(plans, multi) if m for d in p.refine]
        base_n = np.array([d.n for d in base], np.int64)
        refine_n = np.array([d.n for d in refine], np.int64)
        L = fleet_cap(max(int(lens.max()), 1))
        # sample_cap is monotone in n: the largest DC sets the bucket
        cap = sample_cap(int(base_n.max(initial=1)), cfg.cap)
        rcap = sample_cap(int(refine_n.max(initial=1)), cfg.cap)
        F = base[0].x.shape[1] if base else 1

        rshape = (W, L) if cfg.algo == "a2a" else (W,)
        cb = _slot_counts((W, L), lens, base_n, cap)
        cr = _slot_counts(rshape, rlens, refine_n, rcap)
        n_base, n_rows = int(cb.sum()), int(cb.sum() + cr.sum())
        xb_start, b_slots = _placement(cb, cap, 0)
        xr_start, r_slots = _placement(cr, rcap, n_base)
        x_rows = np.zeros((_row_bucket(n_rows), F), np.float32)
        if base:
            np.concatenate([d.x[:cap] for d in base]
                           + [d.x[:rcap] for d in refine],
                           out=x_rows[:n_rows])
        yb = np.zeros((W, L, cap), np.int32)
        mb = np.zeros((W, L, cap), np.float32)
        yr = np.zeros(rshape + (rcap,), np.int32)
        mr = np.zeros(rshape + (rcap,), np.float32)
        for y, m, slots, dcs, k in ((yb, mb, b_slots, base, cap),
                                    (yr, mr, r_slots, refine, rcap)):
            if dcs:
                y.reshape(-1)[slots] = np.concatenate([d.y[:k] for d in dcs])
            m.reshape(-1)[slots] = 1.0

        slot = np.arange(max(L, M_CAP))
        n_pool = np.array([p.n_pool for p in plans]) * multi
        prev = np.where(multi, [p.prev_slot for p in plans], -1)
        out = {"x_rows": x_rows, "xb_start": xb_start, "xb_count": cb,
               "yb": yb, "mb": mb,
               "dcm": (slot[:L] < lens[:, None]).astype(np.float32),
               "xr_start": xr_start, "xr_count": cr, "yr": yr, "mr": mr,
               "src_base": (slot[:M_CAP] < n_pool[:, None]
                            ).astype(np.float32),
               "src_prev": (slot[:M_CAP] == prev[:, None]
                            ).astype(np.float32),
               "n_live": lens.astype(np.float32), "learn": lens > 0,
               "single": np.array([p.single for p in plans], bool)}
        sp.set_metadata(slots=mb.size + mr.size, rows=n_rows,
                        bytes=sum(a.nbytes for a in out.values()))
    return out


def _unpack_block(x_rows, start, count, cap: int):
    """The zero-padded ``(..., cap, F)`` sample block whose slot ``j`` of
    each DC holds table row ``start + j`` for ``j < count``: a gather and a
    select, so every value is the table's own bits or +0.0."""
    j = jnp.arange(cap, dtype=jnp.int32)
    valid = j < count[..., None]
    rows = x_rows[jnp.where(valid, start[..., None] + j, 0)]
    return jnp.where(valid[..., None], rows, jnp.float32(0.0))


def _scan_inputs(x_rows, inp):
    """A window's scan inputs from its slice of the uploaded
    :func:`_pack_plan` arrays (or every window's, from all of them):
    ``xb``/``xr`` built from the row table, the rest as uploaded."""
    xs = dict(inp)
    for blk, lab in (("xb", "yb"), ("xr", "yr")):
        xs[blk] = _unpack_block(x_rows, xs.pop(blk + "_start"),
                                xs.pop(blk + "_count"), xs[lab].shape[-1])
    return xs


# ---------------------------------------------------------------------------
# paper-scale scan engine: the jitted program
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _scan_program(algo: str, num_classes: int, iters: int,
                  trim: float = 0.0):
    """One jitted lax.scan over windows; jit re-specializes per block shape
    (W, L, cap, rcap, N), all of which are bucketed, so the executable cache
    stays small across a sweep. ``trim`` > 0 swaps the A2A combine for the
    coordinate-wise trimmed mean (robust_agg="trim:frac=..."); the trace
    branches at Python level, so ``trim == 0`` compiles the exact
    pre-robust combine graph."""

    def body(carry, inp, eta, x_test, y_oh, x_rows):
        w, has_g = carry
        # each window's blocks are gathered here, not before the scan: the
        # table and one window's blocks stay in the chip's fast memory
        inp = _scan_inputs(x_rows, inp)
        with jax.named_scope("htl.svm"):
            base = jax.vmap(
                lambda xi, yi, mi: _train_svm(xi, yi, mi,
                                              num_classes=num_classes,
                                              iters=iters)
            )(inp["xb"], inp["yb"], inp["mb"])           # (L, F+1, C)
        with jax.named_scope("htl.greedytl"):
            w2, has2 = refine_and_combine(w, has_g, base, inp, eta)
        with jax.named_scope("htl.eval"):
            cm = _window_cm(w2, x_test, y_oh, num_classes)
        return (w2, has2), cm

    def refine_and_combine(w, has_g, base, inp, eta):
        """GreedyTL over the source pool, the combine and the global
        update: the window's new global model and its flag."""
        L = base.shape[0]
        basep = (base[:M_CAP] if L >= M_CAP else
                 jnp.concatenate([base, jnp.zeros((M_CAP - L,) +
                                                  base.shape[1:])], axis=0))
        # masked pool build is exact: x + 0 == x bitwise
        src = (basep * inp["src_base"][:, None, None]
               + w[None] * inp["src_prev"][:, None, None])
        src_mask = inp["src_base"] + inp["src_prev"]
        if algo == "a2a":
            refined = jax.lax.map(
                lambda t3: _greedytl(t3[0], t3[1], t3[2], src, src_mask,
                                     num_classes=num_classes)[0],
                (inp["xr"], inp["yr"], inp["mr"]))       # (L, F+1, C)
            nl = jnp.maximum(inp["n_live"], 1.0)
            if trim > 0.0:
                # trimmed-mean combine over the LIVE rows only: dead and
                # padding rows are pushed past every finite value so the
                # per-window sort stacks them at the top, then the kept
                # band [k, n_live - k) is averaged — the device analogue
                # of repro.core.metrics.trimmed_mean (F1 parity with the
                # host engines is at prediction level, like the mean path)
                big = jnp.float32(3.4e38)
                vals = jnp.where(inp["dcm"][:, None, None] > 0,
                                 refined, big)
                srt = jnp.sort(vals, axis=0)
                k = jnp.floor(jnp.float32(trim) * nl)
                pos = jnp.arange(refined.shape[0], dtype=jnp.float32)
                keep = ((pos >= k) & (pos < nl - k)).astype(refined.dtype)
                multi_new = (jnp.einsum("l,lfc->fc", keep, srt)
                             / jnp.maximum(nl - 2.0 * k, 1.0))
            else:
                multi_new = jnp.einsum("l,lfc->fc", inp["dcm"], refined) / nl
        else:
            multi_new = _greedytl(inp["xr"], inp["yr"], inp["mr"], src,
                                  src_mask, num_classes=num_classes)[0]
        single_new = jnp.where(has_g, 0.5 * (base[0] + w), base[0])
        new = jnp.where(inp["single"], single_new, multi_new)
        upd = jnp.where(has_g, (1.0 - eta) * w + eta * new, new)
        w2 = jnp.where(inp["learn"], upd, w)
        return w2, has_g | inp["learn"]

    @jax.jit
    def program(inputs, eta, x_test, y_oh):
        inputs = dict(inputs)
        x_rows = inputs.pop("x_rows")
        w0 = jnp.zeros((x_rows.shape[-1] + 1, num_classes), jnp.float32)
        carry0 = (w0, jnp.asarray(False))
        _, cms = jax.lax.scan(
            partial(body, eta=eta, x_test=x_test, y_oh=y_oh, x_rows=x_rows),
            carry0, inputs)
        return cms

    return program


@count_dispatch("scan_windows")
def _dispatch_scan(program, inputs, eta, x_test, y_oh):
    return program(inputs, eta, x_test, y_oh)


def run_scenario_scan(cfg, data: Dataset):
    """The whole scenario as ONE jitted dispatch (parity path of the scan
    engine — ledgers exactly equal to the fleet engine's, F1 through the
    streamed confusion counts)."""
    from repro.core.scenario import ScenarioResult, resolve_robust

    with span("scenario", windows=cfg.windows):
        plans, ledger = _plan_scenario(cfg, data)
        packed = _pack_plan(cfg, plans)
        with span("upload", bytes=sum(a.nbytes for a in packed.values())):
            inputs = jax.tree.map(jnp.asarray, packed)
            eta = jnp.float32(cfg.global_update_rate)
            x_test, y_oh = _eval_arrays(data)
        program = _scan_program(cfg.algo, NUM_CLASSES, cfg.train_iters,
                                resolve_robust(cfg.robust_agg))
        cms = _dispatch_scan(program, inputs, eta, x_test, y_oh)
        with span("fetch"):
            cms = np.asarray(cms)
        with span("result"):
            return ScenarioResult(_f1_curve(cms, cfg.eval_every), ledger,
                                  cfg)


# ---------------------------------------------------------------------------
# city engine: 10^5-DC StarHTL, device-resident, shard_map'd DC axis
# ---------------------------------------------------------------------------

def city_fleet_pad(fleet_size: int) -> int:
    """Padded city DC capacity: the PR-1 bucket policy (multiples of 32),
    which every power-of-two shard count <= 32 divides."""
    return fleet_cap(fleet_size)


def _city_round(w, has_g, x, y, m, alive, gid, l0, eta, x_test, y_oh, *,
                num_classes: int, iters: int, shards: int):
    """One city StarHTL round; identical math sharded or not. ``x``/``y``/
    ``m`` are this window's per-DC datasets (local shard rows), ``gid`` the
    global DC ids, ``alive`` the churn-aware membership mask (valid AND
    battery not yet depleted — without churn it equals the plain validity
    mask and every value below is bitwise what it was pre-churn). All
    cross-DC combination is either an exact one-hot psum (source pool,
    center dataset) or a lexicographic max (entropy election), so the
    round is bitwise shard-count invariant. Returns ``(w2, cm, cg, do)``
    where ``do`` flags whether a learning round ran (>= 2 DCs alive; a
    churned-to-nothing fleet keeps ``w`` untouched)."""
    K = x.shape[1]
    base = jax.vmap(
        lambda xi, yi, mi: _train_svm(xi, yi, mi, num_classes=num_classes,
                                      iters=iters))(x, y, m)

    # entropy-based center election (paper Sec. 4), lexicographic tie-break
    # on the global DC id so every shard layout elects the same center
    cnt = jnp.sum(jax.nn.one_hot(y, num_classes) * m[:, :, None], axis=1)
    tot = jnp.maximum(jnp.sum(cnt, axis=1), 1.0)
    p = cnt / tot[:, None]
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(p), 0.0), axis=1) \
        / jnp.log(float(num_classes))
    ent = jnp.where(alive, ent, -1.0)
    li = jnp.argmax(ent)                       # first max = lowest local gid
    ce, cg = ent[li], gid[li]
    n_alive = jnp.sum(alive.astype(jnp.float32))
    if shards > 1:
        es = jax.lax.all_gather(ce, FLEET_AXIS)
        gs = jax.lax.all_gather(cg, FLEET_AXIS)
        ce, cg = es[0], gs[0]
        for i in range(1, shards):
            better = (es[i] > ce) | ((es[i] == ce) & (gs[i] < cg))
            ce = jnp.where(better, es[i], ce)
            cg = jnp.where(better, gs[i], cg)
        n_alive = jax.lax.psum(n_alive, FLEET_AXIS)

    # source pool: base models of the first min(L0, M_CAP) *alive* DCs'
    # slots, gathered by exact one-hot psum (x + 0 == x bitwise); the mask
    # is the same one-hot reduced, so dead DCs' slots leave the pool (with
    # nobody dead it reduces to exactly the old ``slot < min(l0, M_CAP)``)
    slot = jnp.arange(M_CAP, dtype=gid.dtype)
    oh = ((gid[:, None] == slot[None, :]) & (slot[None, :] < l0)
          & alive[:, None]).astype(jnp.float32)
    src = jnp.einsum("lm,lfc->mfc", oh, base)
    src_mask = jnp.sum(oh, axis=0)

    # center's local dataset, same exact one-hot reduction
    coh = (gid == cg).astype(jnp.float32)
    cx = jnp.einsum("l,lkf->kf", coh, x)
    cy = jnp.einsum("l,lk->k", coh, y.astype(jnp.float32))
    if shards > 1:
        src = jax.lax.psum(src, FLEET_AXIS)
        src_mask = jax.lax.psum(src_mask, FLEET_AXIS)
        cx = jax.lax.psum(cx, FLEET_AXIS)
        cy = jax.lax.psum(cy, FLEET_AXIS)

    refined, _ = _greedytl(cx, cy.astype(jnp.int32), jnp.ones((K,)),
                           src, src_mask, num_classes=num_classes)
    do = n_alive >= 2.0
    upd = jnp.where(has_g, (1.0 - eta) * w + eta * refined, refined)
    w2 = jnp.where(do, upd, w)
    cm = _window_cm(w2, x_test, y_oh, num_classes)
    return w2, cm, cg, do


def _draw_window(xtr, ytr, key, t, gid, validf, obs_per_dc: int):
    """Device-side collection: per-DC fold_in keys (shard-count invariant),
    ``obs_per_dc`` uniform draws from the train stream per DC."""
    n_train = xtr.shape[0]
    kt = jax.random.fold_in(key, t)
    keys = jax.vmap(lambda g: jax.random.fold_in(kt, g))(gid)
    idx = jax.vmap(
        lambda k: jax.random.randint(k, (obs_per_dc,), 0, n_train))(keys)
    x = xtr[idx]                                # (Lloc, K, F)
    y = ytr[idx]
    m = jnp.ones(idx.shape, jnp.float32) * validf[:, None]
    return x, y, m


@lru_cache(maxsize=None)
def _city_program(W: int, L: int, K: int, shards: int, num_classes: int,
                  iters: int):
    """The whole city scenario as one jitted shard_map'd scan: collection,
    training, election, refine, EMA and streamed eval never leave the
    device; per-window buffers are scan-local, so peak memory is
    independent of W."""
    mesh = fleet_mesh(shards)
    Lloc = L // shards

    def mapped(xtr, ytr, x_test, y_oh, eta, l0, key, t_die):
        shard = jax.lax.axis_index(FLEET_AXIS).astype(jnp.int32)
        gid = shard * Lloc + jnp.arange(Lloc, dtype=jnp.int32)
        valid = gid < l0
        # per-DC death window (churn; W everywhere = nobody ever dies, so
        # alive == valid and every window computes its pre-churn values)
        t_die_loc = jnp.take(t_die, gid)

        def body(carry, t):
            w, has_g = carry
            alive = valid & (t < t_die_loc)
            alivef = alive.astype(jnp.float32)
            x, y, m = _draw_window(xtr, ytr, key, t, gid, alivef, K)
            w2, cm, cg, do = _city_round(
                w, has_g, x, y, m, alive, gid, l0, eta, x_test, y_oh,
                num_classes=num_classes, iters=iters, shards=shards)
            return (w2, has_g | do), (cm, cg)

        F = xtr.shape[1]
        carry0 = (jnp.zeros((F + 1, num_classes), jnp.float32),
                  jnp.asarray(False))
        _, (cms, centers) = jax.lax.scan(body, carry0,
                                         jnp.arange(W, dtype=jnp.int32))
        return cms, centers

    fn = jax.shard_map(mapped, mesh=mesh,
                       in_specs=(P(), P(), P(), P(), P(), P(), P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    return jax.jit(fn)


@count_dispatch("city_scan")
def _dispatch_city(program, *args):
    return program(*args)


def _charge_city_collection(ledger: Ledger, fleet_size: int,
                            obs_per_dc: int) -> None:
    """One aggregate collection event per window: every DC collects
    ``obs_per_dc`` observations over 802.15.4 (1 tx + 1 rx each), charged
    as event counts so the total equals ``fleet_size`` separate
    ``collect_to_mule`` events."""
    ledger.add("802.15.4", obs_per_dc * OBS_BYTES, purpose="collection",
               n_tx=fleet_size, n_rx=fleet_size, what="sensor->SM (city)")


def _charge_city_learning(ledger: Ledger, tech: str, fleet_size: int,
                          center_is_ap: bool) -> None:
    """Analytic StarHTL learning charge for one window: the loop/fleet
    engines iterate Topology patterns over L(L-1) ordered pairs; at city
    scale we evaluate the transport's per-role-pair (tx, rx) counts on
    three representative nodes and multiply by the pair multiplicities —
    O(1) ledger events per window, totals equal to the pairwise sum."""
    L = fleet_size
    counts = get_transport(tech).counts
    ap, m1, m2 = Node("AP", is_ap=True), Node("SM1"), Node("SM2")

    def add(nbytes, what, pairs):
        tx = rx = 0
        for mult, src, dst in pairs:
            a, b = counts(src, dst)
            tx += mult * a
            rx += mult * b
        ledger.add(tech, nbytes, purpose="learning", n_tx=tx, n_rx=rx,
                   what=what)

    # entropy index exchange: every ordered pair
    add(INDEX_BYTES, "entropy index",
        [(L - 1, ap, m1), (L - 1, m1, ap), ((L - 1) * (L - 2), m1, m2)])
    if center_is_ap:
        add(INDEX_BYTES, "center id", [(L - 1, ap, m1)])
        add(MODEL_BYTES, "m0 to center", [(L - 1, m1, ap)])
    else:
        add(INDEX_BYTES, "center id", [(1, m1, ap), (L - 2, m1, m2)])
        add(MODEL_BYTES, "m0 to center", [(1, ap, m1), (L - 2, m2, m1)])


def _city_death_schedule(cfg, L0: int, L: int) -> np.ndarray:
    """Per-DC death windows of the city churn model (DC ``i`` is alive for
    windows ``t < t_die[i]``; ``windows`` everywhere = nobody ever dies).

    Batteries are heterogeneous — ``battery_mj * (0.5 + U[0, 1))`` per DC
    from a dedicated seeded stream, so depletion staggers instead of the
    whole fleet dying at once — and drain per window is the analytic
    per-DC share of the city charging model (collection rx + learning
    total / L0), evaluated once up front. The schedule is therefore a
    deterministic function of (seed, battery_mj, tech, fleet shape),
    identical across shard counts by construction — the device side only
    ever sees the precomputed ``t_die`` array."""
    W = cfg.windows
    t_die = np.full((L,), W, np.int32)
    if cfg.battery_mj is None:
        return t_die
    from repro.core.energy import resolve_tech
    drng = np.random.default_rng([int(cfg.seed), 0xC17B])
    batt = cfg.battery_mj * (0.5 + drng.random(L0))
    tmp = Ledger()
    _charge_city_learning(tmp, cfg.tech, L0, center_is_ap=False)
    e_w = (resolve_tech("802.15.4").rx_mj(cfg.obs_per_dc * OBS_BYTES)
           + tmp.total() / L0)
    t_die[:L0] = np.minimum(W, np.ceil(batt / e_w)).astype(np.int32)
    return t_die


def city_outputs(cfg, data: Dataset, *, max_shards: Optional[int] = None
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The city program's raw per-window outputs: confusion counts
    ``(W, C, C)``, elected centre gids ``(W,)`` and the death schedule
    ``t_die`` it ran with. ``max_shards`` caps the DC-mesh width (default:
    every visible device whose count divides the padded fleet)."""
    L0, K, W = cfg.fleet_size, cfg.obs_per_dc, cfg.windows
    L = city_fleet_pad(L0)
    shards = dc_shards(L, max_shards)
    xtr, ytr = _train_arrays(data)
    x_test, y_oh = _eval_arrays(data)
    t_die = _city_death_schedule(cfg, L0, L)
    program = _city_program(W, L, K, shards, NUM_CLASSES, cfg.train_iters)
    cms, centers = _dispatch_city(
        program, xtr, ytr, x_test, y_oh,
        jnp.float32(cfg.global_update_rate), jnp.int32(L0),
        jax.random.PRNGKey(cfg.seed), jnp.asarray(t_die))
    return np.asarray(cms), np.asarray(centers), t_die


def run_city(cfg, data: Dataset, *, max_shards: Optional[int] = None):
    """The city scenario: ``cfg.fleet_size`` DCs, ``cfg.obs_per_dc``
    observations each per window, StarHTL, one jitted dispatch for the
    whole run (:func:`city_outputs`), energy charged analytically."""
    from repro.core.scenario import ScenarioResult

    L0, K, W = cfg.fleet_size, cfg.obs_per_dc, cfg.windows
    cms, centers, t_die = city_outputs(cfg, data, max_shards=max_shards)

    ledger = Ledger()
    for t in range(W):
        alive = t < t_die[:L0]
        n_alive = int(alive.sum())
        if n_alive > 0:
            _charge_city_collection(ledger, n_alive, K)
        if n_alive >= 2:
            # the analytic AP role falls to the lowest-gid alive DC
            ap_gid = int(np.argmax(alive))
            _charge_city_learning(ledger, cfg.tech, n_alive,
                                  center_is_ap=(int(centers[t]) == ap_gid))
    return ScenarioResult(_f1_curve(cms, cfg.eval_every), ledger, cfg)


# ---------------------------------------------------------------------------
# per-window city reference: host-driven loop, one dispatch + one host sync
# per window, host-side collection shipped to device every window — the
# pre-scan execution pattern, kept as the benchmark comparator
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _city_round_program(num_classes: int, iters: int):
    @jax.jit
    def fn(w, has_g, x, y, m, alive, gid, l0, eta, x_test, y_oh):
        return _city_round(w, has_g, x, y, m, alive, gid, l0, eta,
                           x_test, y_oh, num_classes=num_classes,
                           iters=iters, shards=1)
    return fn


def run_city_perwindow(cfg, data: Dataset):
    """City scenario on the per-window pattern: every window the host draws
    the fleet's observations, packs and uploads them, dispatches one round
    and syncs the global model back — wall-clock scales with
    ``windows x fleet data volume`` where :func:`run_city` pays one
    dispatch total. Results match :func:`run_city` to float roundoff (the
    rng streams differ by design: host numpy vs device fold_in)."""
    from repro.core.scenario import ScenarioResult

    L0, K, W = cfg.fleet_size, cfg.obs_per_dc, cfg.windows
    L = city_fleet_pad(L0)
    rng = np.random.default_rng(cfg.seed)
    xtr_host = data.x_train.astype(np.float32)
    ytr_host = data.y_train.astype(np.int32)
    x_test, y_oh = _eval_arrays(data)
    gid = jnp.arange(L, dtype=jnp.int32)
    valid_host = np.arange(L) < L0
    t_die = _city_death_schedule(cfg, L0, L)
    program = _city_round_program(NUM_CLASSES, cfg.train_iters)

    ledger = Ledger()
    w = np.zeros((xtr_host.shape[1] + 1, NUM_CLASSES), np.float32)
    has_g = False
    cms = np.zeros((W, NUM_CLASSES, NUM_CLASSES), np.float32)
    for t in range(W):
        alive_host = valid_host & (t < t_die)
        m_host = np.broadcast_to(alive_host[:, None], (L, K)
                                 ).astype(np.float32).copy()
        idx = rng.integers(0, len(ytr_host), size=(L, K))
        xw = xtr_host[idx]                     # host gather, uploaded fresh
        yw = ytr_host[idx]
        w_dev, cm, cg, do = program(jnp.asarray(w), jnp.asarray(has_g),
                                    jnp.asarray(xw), jnp.asarray(yw),
                                    jnp.asarray(m_host),
                                    jnp.asarray(alive_host),
                                    gid, jnp.int32(L0),
                                    jnp.float32(cfg.global_update_rate),
                                    x_test, y_oh)
        w = np.asarray(w_dev)                  # per-window host sync
        has_g = bool(has_g or bool(do))
        cms[t] = np.asarray(cm)
        n_alive = int(alive_host.sum())
        if n_alive > 0:
            _charge_city_collection(ledger, n_alive, K)
        if n_alive >= 2:
            ap_gid = int(np.argmax(alive_host))
            _charge_city_learning(ledger, cfg.tech, n_alive,
                                  center_is_ap=(int(cg) == ap_gid))
    return ScenarioResult(_f1_curve(cms, cfg.eval_every), ledger, cfg)
