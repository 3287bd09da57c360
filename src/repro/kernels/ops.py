"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on TPU backends the kernels run compiled (Mosaic); on any
other backend (this CPU container) they run with ``interpret=True`` — the
kernel body executes in Python per grid cell, which is what the correctness
sweeps in tests/test_kernels.py rely on. Model code selects these via
``ModelConfig.attention_impl = 'pallas'``; the dry-run keeps the XLA
reference path because Pallas does not lower to CPU HLO.

``loo_trials`` (GreedyTL's greedy-loop hot path) is selected DATA-DRIVEN
instead: a small autotuner micro-benchmarks the Pallas kernel against the
pure-jnp reference at the bucketed (R, D, M) shapes actually seen, caches
the winner per backend (in memory, and as a JSON table under
``results/benchmarks/kernel_autotune.json`` when persisted by the bench
driver), and tunes ``block_r`` rather than hardcoding 256. The env var
``REPRO_KERNEL_FORCE=pallas|jnp`` overrides the selection outright — CI
pins ``jnp`` so gate results never depend on machine timing noise
(DESIGN.md §11).
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import loo_trials as _loo
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention_bshd(q, k, v, *, causal=True, window=0, q_offset=0):
    """(B,S,H,d) layout wrapper matching `models.blocks.chunked_attention`."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              q_offset=q_offset, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_kv=128):
    """(B,H,S,d) layout."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, block_q=block_q,
                               block_kv=block_kv, interpret=_interpret())


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=_interpret())


def rglru_scan(a, b, *, chunk=128, block_w=128):
    return _rg.rglru_scan(a, b, chunk=chunk, block_w=block_w,
                          interpret=_interpret())


# ---------------------------------------------------------------------------
# loo_trials autotuner: measured jnp-vs-Pallas crossover + tuned block_r
# ---------------------------------------------------------------------------

FORCE_ENV = "REPRO_KERNEL_FORCE"
CACHE_DIR_ENV = "REPRO_KERNEL_CACHE_DIR"
CACHE_FILE = "kernel_autotune.json"
DEFAULT_BLOCK_R = 256
PALLAS_BLOCK_RS = (64, 128, 256, 512)

_tune_lock = threading.Lock()
_tune_mem: dict = {}        # (backend, bucket key) -> winning entry dict
_tune_disk_loaded = False


def kernel_force():
    """Validated REPRO_KERNEL_FORCE value (read per call, so tests and CI
    control it without import-order games)."""
    v = os.environ.get(FORCE_ENV)
    if v in (None, ""):
        return None
    if v not in ("pallas", "jnp"):
        raise ValueError(f"{FORCE_ENV} must be 'pallas' or 'jnp', got {v!r}")
    return v


def _cache_dir() -> Path:
    d = os.environ.get(CACHE_DIR_ENV)
    if d:
        return Path(d)
    # src/repro/kernels/ops.py -> repo root / results / benchmarks
    return Path(__file__).resolve().parents[3] / "results" / "benchmarks"


def bucket_rows(r: int) -> int:
    """Row-count bucket: next power of two, floored at one sublane tile (8).
    Stage-1 row counts are n*C over bucketed sample caps, so a handful of
    buckets covers every shape a sweep dispatches."""
    return max(8, 1 << max(0, int(r) - 1).bit_length())


def autotune_key(r: int, d: int, m: int) -> str:
    return f"R{bucket_rows(r)}_D{int(d)}_M{int(m)}"


def _load_disk_cache_locked() -> None:
    global _tune_disk_loaded
    if _tune_disk_loaded:
        return
    _tune_disk_loaded = True
    try:
        payload = json.loads((_cache_dir() / CACHE_FILE).read_text())
    except (OSError, ValueError):
        return
    for backend, entries in payload.get("backends", {}).items():
        for key, entry in entries.items():
            _tune_mem.setdefault((backend, key), entry)


def _persist_cache_locked() -> None:
    backends: dict = {}
    for (backend, key), entry in sorted(_tune_mem.items()):
        backends.setdefault(backend, {})[key] = entry
    payload = {
        "version": 1,
        "kernel": "loo_trials",
        "note": "per-backend measured impl selection for the GreedyTL "
                "trial-scoring kernel; keys are bucketed (R, D, M) shapes; "
                "regenerate with repro.kernels.ops.autotune_loo_trials("
                "..., persist=True) or benchmarks/run.py",
        "backends": backends,
    }
    path = _cache_dir() / CACHE_FILE
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
        os.replace(tmp, path)
    except OSError:
        pass                      # read-only checkout: memory cache only


def autotune_table(backend=None) -> dict:
    """``{bucket key: entry}`` the autotuner holds for ``backend``
    (default: the current one): the shapes measured in this process plus
    any loaded from the persisted table."""
    backend = backend or jax.default_backend()
    with _tune_lock:
        return {key: dict(e) for (b, key), e in sorted(_tune_mem.items())
                if b == backend}


def reset_autotune_cache() -> None:
    """Drop the in-memory cache and force a disk reload (test hook)."""
    global _tune_disk_loaded
    with _tune_lock:
        _tune_mem.clear()
        _tune_disk_loaded = False


def _default_candidates(backend: str):
    """(impl, block_r) candidates worth measuring on this backend. Off-TPU
    the compiled Mosaic path does not exist and interpret mode is orders of
    magnitude off the production regime, so jnp is the only honest
    candidate — the autotuner then just measures and records it."""
    cands = [("jnp", 0)]
    if backend == "tpu":
        cands += [("pallas", br) for br in PALLAS_BLOCK_RS]
    return cands


def _time_call(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))                 # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def autotune_loo_trials(r: int, d: int, m: int, *, backend=None,
                        persist: bool = False, refresh: bool = False,
                        candidates=None, reps: int = 5) -> dict:
    """Measure every candidate ``loo_trials`` implementation at the bucketed
    (r, d, m) shape and cache the winner.

    Returns the winning entry ``{"impl", "block_r", "timings_us", "shape"}``.
    Cached per backend; ``persist=True`` additionally writes the JSON table
    under results/benchmarks/ (the runtime path never writes — only the
    bench driver and explicit callers do, so test runs leave the repo
    clean). ``candidates`` overrides the measured set (tests use it to
    force tiny interpret-mode Pallas runs off-TPU)."""
    backend = backend or jax.default_backend()
    key = autotune_key(r, d, m)
    with _tune_lock:
        _load_disk_cache_locked()
        hit = _tune_mem.get((backend, key))
    if hit is not None and not refresh:
        # a memory hit must still reach the disk table: the runtime path
        # pre-populates buckets (memory-only) before the bench persists
        if persist:
            with _tune_lock:
                _persist_cache_locked()
        return hit

    rb, d, m = bucket_rows(r), int(d), int(m)
    rng = np.random.default_rng(0)
    f32 = np.float32
    args = tuple(jnp.asarray(v) for v in (
        rng.standard_normal((rb, d)).astype(f32),          # ut
        rng.standard_normal((d, m)).astype(f32),           # cc
        rng.standard_normal((rb, m)).astype(f32),          # a_cand
        rng.standard_normal(rb).astype(f32),               # fitted_base
        np.abs(rng.standard_normal(rb)).astype(f32) * 0.1,  # h_base
        rng.standard_normal(rb).astype(f32),               # y
        (rng.random(rb) < 0.8).astype(f32),                # rmask
        rng.standard_normal(m).astype(f32),                # zj
        np.abs(rng.standard_normal(m)).astype(f32),        # dinv
    ))

    cands = (candidates if candidates is not None
             else _default_candidates(backend))
    if not cands:
        raise ValueError("autotune_loo_trials needs at least one candidate")
    timings = {}
    # a candidate that fails to compile raises: on the chip a kernel that
    # Mosaic refuses must surface, not quietly become the jnp path
    for impl, br in cands:
        if impl == "jnp":
            label, fn = "jnp", jax.jit(_loo.loo_trials_ref)
        else:
            label = f"pallas@{br}"
            fn = functools.partial(_loo.loo_trials, block_r=br,
                                   interpret=backend != "tpu")
        timings[label] = round(_time_call(fn, args, reps), 2)
    best = min(timings, key=timings.get)
    entry = {
        "impl": "jnp" if best == "jnp" else "pallas",
        "block_r": 0 if best == "jnp" else int(best.split("@")[1]),
        "timings_us": timings,
        "shape": [rb, d, m],
        "reps": reps,
    }
    with _tune_lock:
        _tune_mem[(backend, key)] = entry
        if persist:
            _persist_cache_locked()
    return entry


def loo_trials(ut, cc, a_cand, fitted_base, h_base, y, rmask, zj, dinv):
    """GreedyTL Cholesky-bordering trial scorer (see kernels.loo_trials).

    Selection is autotuned (see module doc): the measured winner for this
    (R, D, M) bucket on this backend runs, with its tuned ``block_r``.
    ``REPRO_KERNEL_FORCE`` short-circuits the tuner: ``jnp`` always takes
    the pure-jnp reference; ``pallas`` always takes the kernel (interpret
    mode off-TPU — correctness-path only, used by the CI parity test).
    Shapes are static at trace time, so the selection is resolved per
    traced shape and adds nothing to the compiled program."""
    shaped = (ut.shape[0], ut.shape[1], cc.shape[1])
    force = kernel_force()
    if force == "jnp":
        return _loo.loo_trials_ref(ut, cc, a_cand, fitted_base, h_base, y,
                                   rmask, zj, dinv)
    if force == "pallas":
        entry = _tune_mem.get((jax.default_backend(),
                               autotune_key(*shaped)))
        br = (entry or {}).get("block_r") or DEFAULT_BLOCK_R
        return _loo.loo_trials(ut, cc, a_cand, fitted_base, h_base, y,
                               rmask, zj, dinv, block_r=br,
                               interpret=_interpret())
    entry = autotune_loo_trials(*shaped)
    if entry["impl"] == "pallas" and not _interpret():
        return _loo.loo_trials(ut, cc, a_cand, fitted_base, h_base, y,
                               rmask, zj, dinv, block_r=entry["block_r"])
    return _loo.loo_trials_ref(ut, cc, a_cand, fitted_base, h_base, y,
                               rmask, zj, dinv)
