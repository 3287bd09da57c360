"""Device-/process-sharded sweep execution with bitwise-parity guarantees.

The paper's headline numbers are sweeps (Tables 2-6: algorithm x technology
x ``p_edge`` x aggregation x seeds), and until now a sweep ran its
stacking groups sequentially on one host. This module scales the grid out
while keeping the repo's reproducibility contract — a parallel run must be
*JSON-identical* to the sequential run, so parallelism can never change a
published table:

* :func:`partition_runs` — a deterministic partitioner over
  ``SweepSpec.configs()`` rows. Rows are grouped by
  :func:`repro.core.scenario.stack_key` (groups are **never split** across
  shards, so every shard keeps its replica-stacking wins), each group is
  costed at ``windows x replicas`` (:func:`run_cost`), and groups are
  placed greedy-LPT onto the least-loaded shard. Group order is derived
  from (cost, canonical key) — not input order — so the partition is
  invariant to row permutations (tests/test_parallel_sweep.py).
* two execution backends behind the shared spec-string grammar of
  :mod:`repro.core.registry` (``get_executor("devices:n=8")``):

  - ``devices`` — shards run concurrently from one thread per shard, each
    pinned to a ``jax.devices()`` entry via ``jax.default_device`` (the
    stacked replica axis of every group stays whole on its shard's
    device). Testable on CPU via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
  - ``processes`` — a spawn-based worker pool runs whole shards and ships
    each shard's :class:`~repro.core.experiment.SweepResult` back as a
    JSON payload (plus its jitted-dispatch counts); the parent merges
    payloads into one order-stable result. Worker traffic is guarded by
    :func:`assert_host_only`: no jax device buffers ever cross the pool
    boundary, and per-worker jit/eval caches are process-isolated by
    construction.

Both backends run every group through exactly the same stacked engines in
exactly the same within-group order as ``parallel="none"``, so results are
bitwise identical, not merely close (the parallel-parity gate in
scripts/verify.sh diffs the serialized JSON). Dispatch counts are threaded
back to the parent counter (:func:`repro.core.dispatch.
merge_dispatch_counts`), so the O(buckets)-dispatches-per-window CI gate
holds per shard too. See DESIGN.md §7.

A third out-of-process backend, ``hosts`` (:mod:`repro.core.launcher`,
DESIGN.md §8), scales the same partition/merge beyond one machine:
shards ship as JSON payloads produced by the shared shard runner
(:func:`run_shard_payload`) to local-subprocess / ssh / slurm worker
channels, with shard-level retry on worker loss — same bitwise contract,
gated by scripts/hosts_parity.py.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.dispatch import dispatch_counts, merge_dispatch_counts
from repro.core.registry import register_factory, resolve_spec
from repro.core.scenario import (ScenarioConfig, ScenarioResult, run_sweep,
                                 stack_groups, stack_key)
from repro.data.synthetic_covtype import Dataset


# ---------------------------------------------------------------------------
# cost model + partitioner
# ---------------------------------------------------------------------------

def run_cost(cfg: ScenarioConfig) -> float:
    """Estimated cost of one run: its window count. A stacking group of R
    replicas therefore costs ``windows x R`` — the group runs one stacked
    dispatch set per window, and per-window host work grows with R."""
    return float(cfg.windows)


def partition_runs(cfgs: Sequence[ScenarioConfig], n_shards: int, *,
                   key_fn: Callable[[ScenarioConfig], Any] = stack_key,
                   cost_fn: Callable[[ScenarioConfig], float] = run_cost
                   ) -> List[List[int]]:
    """Split run indices into ``n_shards`` shards, stack-key groups atomic.

    Contract (property-tested):

    * every index appears in exactly one shard;
    * rows with equal ``key_fn`` stay on one shard (so replica stacking
      inside :func:`~repro.core.scenario.run_sweep` sees the same groups a
      sequential run would);
    * greedy LPT balance: the max shard cost is at most twice the ideal
      ``max(total / n_shards, max_group_cost)``;
    * the grouping of configs onto shards is invariant to the input order
      of the rows (groups are placed in (cost desc, canonical key) order,
      never first-appearance order).

    Shards may be empty when there are fewer groups than shards. Within a
    shard, indices stay ascending, so per-shard execution preserves the
    original relative run order.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    placed = sorted(
        ((sum(cost_fn(cfgs[i]) for i in idxs),
          repr(key_fn(cfgs[idxs[0]])), idxs)
         for idxs in stack_groups(cfgs, key_fn)),
        key=lambda rec: (-rec[0], rec[1]))
    loads = [0.0] * n_shards
    shards: List[List[int]] = [[] for _ in range(n_shards)]
    for cost, _, idxs in placed:
        k = min(range(n_shards), key=lambda j: loads[j])
        loads[k] += cost
        shards[k].extend(idxs)
    for s in shards:
        s.sort()
    return shards


# ---------------------------------------------------------------------------
# host-only payload guard (the process-pool boundary)
# ---------------------------------------------------------------------------

def assert_host_only(obj: Any, where: str = "payload") -> None:
    """Refuse jax device buffers in inter-process payloads.

    Pickling a ``jax.Array`` drags a device buffer (and on real hardware a
    device sync) through the worker queue; every array crossing the pool
    boundary must be host-side numpy. Walks nested containers; numpy
    arrays, dataclass-like plain values and strings pass."""
    import jax

    stack = [obj]
    while stack:
        o = stack.pop()
        if isinstance(o, jax.Array):
            raise TypeError(
                f"jax device buffer in inter-process {where}: "
                f"{type(o).__name__} with shape {getattr(o, 'shape', '?')}; "
                f"convert to numpy before crossing the pool boundary")
        if isinstance(o, np.ndarray):
            continue
        if isinstance(o, dict):
            stack.extend(o.keys())
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        elif dataclasses_fields := getattr(o, "__dataclass_fields__", None):
            stack.extend(getattr(o, f) for f in dataclasses_fields)


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

class ChipHeldError(RuntimeError):
    """A backend that starts child interpreters was asked to run where this
    process holds a TPU: the children would fail or hang on the chip."""


def refuse_child_interpreters(backend: str) -> None:
    """Raise :class:`ChipHeldError` when this process runs on a TPU.

    A TPU belongs to one process at a time, and a process that has
    touched JAX holds it; the spawned pool (``processes``) and the
    ``hosts:channel=local`` workers would each need the chip again. On a
    TPU host, run sweeps with ``none``, ``devices`` or
    ``hosts:channel=inline``, which stay in this process."""
    import jax

    if jax.default_backend() == "tpu":
        raise ChipHeldError(
            f"{backend} starts child interpreters, but the TPU belongs to "
            f"one process and this one holds it; use parallel='none', "
            f"'devices:n=K' or 'hosts:channel=inline' on a TPU host")


# ---------------------------------------------------------------------------
# execution backends
# ---------------------------------------------------------------------------

class SweepExecutor:
    """Backend protocol: evaluate labelled runs, results in input order."""

    def execute(self, labels: Sequence[str],
                cfgs: Sequence[ScenarioConfig], data: Dataset, *,
                stack: bool) -> List[ScenarioResult]:
        raise NotImplementedError

    def execute_with_meta(self, labels: Sequence[str],
                          cfgs: Sequence[ScenarioConfig], data: Dataset, *,
                          stack: bool
                          ) -> Tuple[List[ScenarioResult], Dict[str, Any]]:
        """Evaluate and additionally return execution metadata (attempt
        logs, channel info, ...) for ``SweepResult.meta``. Metadata is a
        side channel: it never enters the serialized result, so backends
        that populate it keep the bitwise-parity contract intact. The
        default backend has nothing to report."""
        return self.execute(labels, cfgs, data, stack=stack), {}


class _SequentialExecutor(SweepExecutor):
    """``parallel="none"``: the existing single-host path, verbatim."""

    def execute(self, labels, cfgs, data, *, stack):
        return run_sweep(list(cfgs), data, stack_seeds=stack)


class _DeviceShardExecutor(SweepExecutor):
    """``parallel="devices:n=K"``: K shards, one thread per shard, each
    pinned to a ``jax.devices()`` entry (round-robin when K exceeds the
    device count). Every shard runs the standard stacked ``run_sweep``
    under ``jax.default_device``, so the computation per group is the
    sequential computation placed on a different device — values are
    bitwise identical, only placement and overlap change."""

    def __init__(self, n: Optional[int] = None):
        if n is not None and n < 1:
            raise ValueError(f"devices executor needs n >= 1, got {n}")
        self.n = n

    def execute(self, labels, cfgs, data, *, stack):
        import jax

        devices = jax.devices()
        n = self.n if self.n is not None else len(devices)
        shards = [s for s in partition_runs(cfgs, n) if s]
        results: List[Optional[ScenarioResult]] = [None] * len(cfgs)

        def run_shard(k: int) -> List[ScenarioResult]:
            with jax.default_device(devices[k % len(devices)]):
                return run_sweep([cfgs[i] for i in shards[k]], data,
                                 stack_seeds=stack)

        if len(shards) <= 1:
            outs = [run_shard(k) for k in range(len(shards))]
        else:
            workers = max(1, min(len(shards), len(devices)))
            with ThreadPoolExecutor(max_workers=workers) as pool:
                outs = list(pool.map(run_shard, range(len(shards))))
        for idxs, rs in zip(shards, outs):
            for i, r in zip(idxs, rs):
                results[i] = r
        return results


def run_shard_payload(labels: Sequence[str], cfgs: Sequence[ScenarioConfig],
                      data: Dataset, stack: bool) -> Tuple[str, dict]:
    """Run one whole shard and return its transport-agnostic wire form:
    the shard's :class:`~repro.core.experiment.SweepResult` serialized as
    JSON plus the jitted-dispatch counts the shard incurred. This is the
    single shard-runner shared by every out-of-process backend — the
    spawn-pool worker below and the multi-host launcher workers
    (:mod:`repro.core.launcher`) — so the payload schema cannot drift
    between transports."""
    from repro.core.dispatch import reset_dispatch_counts
    from repro.core.experiment import SweepResult, records_from

    # per-shard counts: one worker may execute several shards, and the
    # parent merges every returned snapshot, so counts must not
    # accumulate across tasks
    reset_dispatch_counts()
    results = run_sweep(list(cfgs), data, stack_seeds=stack)
    records = records_from(labels, results)
    payload = SweepResult(name="shard", records=records).to_json(indent=0)
    return payload, dispatch_counts()


class ShardMerger:
    """Incremental, order-stable merge of per-shard wire payloads.

    The barrier-free counterpart of the all-at-once merge below (and the
    machinery under it): shards write to disjoint run-index slots, so they
    may arrive in *any* order — as NDJSON events stream in from the sweep
    service (:mod:`repro.service`), as launcher retries land late, or
    twice after a client reconnect replays part of a stream — and the
    merged run list is identical to the sequential run's regardless
    (property-tested in tests/test_sweep_service.py). All mutation is
    lock-guarded: one merger may be fed from several streaming jobs'
    threads, and each shard's dispatch counts fold into the process
    counter exactly once even if its payload is replayed."""

    def __init__(self, n_runs: int, shards: Sequence[Sequence[int]]):
        self.shards = [list(s) for s in shards]
        self._results: List[Optional[ScenarioResult]] = [None] * n_runs
        self._done: set = set()
        self._lock = threading.Lock()

    def add(self, shard: int, payload: str, counts: dict) -> bool:
        """Fold one shard's payload in; returns False (and does nothing)
        when that shard was already merged — replays after a reconnect are
        idempotent by construction."""
        from repro.core.experiment import SweepResult

        idxs = self.shards[shard]
        shard_result = SweepResult.from_json(payload)
        if len(shard_result.records) != len(idxs):
            raise ValueError(
                f"shard payload carries {len(shard_result.records)} records "
                f"for a {len(idxs)}-run shard")
        with self._lock:
            if shard in self._done:
                return False
            self._done.add(shard)
            merge_dispatch_counts(counts)
            for i, rec in zip(idxs, shard_result.records):
                self._results[i] = rec.to_scenario_result()
        return True

    def pending(self) -> List[int]:
        with self._lock:
            return [k for k in range(len(self.shards))
                    if k not in self._done]

    def results(self) -> List[ScenarioResult]:
        """The full merged run list; raises if any shard is still missing
        (an incremental merge is only a result once every shard landed)."""
        missing = self.pending()
        if missing:
            raise ValueError(f"shard(s) {missing} not merged yet")
        with self._lock:
            return list(self._results)


def merge_shard_payloads(n_runs: int, shards: Sequence[Sequence[int]],
                         outs: Sequence[Tuple[str, dict]]
                         ) -> List[ScenarioResult]:
    """Order-stable merge of per-shard wire payloads back into the full
    run list: shard k's i-th record lands at the i-th index of shard k's
    partition slot, and every shard's dispatch counts fold into the parent
    counter (so the dispatch CI gate stays observable per shard). Shared
    by the processes backend and the hosts launcher; the streaming sweep
    service merges the same payloads incrementally via
    :class:`ShardMerger` (which this wraps), so the two paths cannot
    drift."""
    merger = ShardMerger(n_runs, shards)
    for k, (payload, counts) in enumerate(outs):
        merger.add(k, payload, counts)
    return merger.results()


def _worker_run_shard(task: Tuple[List[str], List[ScenarioConfig],
                                  Dataset, bool]) -> Tuple[str, dict]:
    """Process-pool worker: run one whole shard via the shared shard
    runner. Runs in a spawned interpreter — jit caches, EvalCache and
    dispatch counters are all process-local, so workers never share (or
    ship) device state."""
    labels, cfgs, data, stack = task
    return run_shard_payload(labels, cfgs, data, stack)


class _ProcessShardExecutor(SweepExecutor):
    """``parallel="processes:n=K"``: a spawn-based pool runs whole shards;
    per-shard ``SweepResult`` JSON payloads merge back order-stably.

    ``spawn`` (not ``fork``) because the parent may hold an initialized
    jax runtime whose internal threads do not survive forking. Inbound
    payloads are host-only (:func:`assert_host_only`), and the shard
    result travels back as serialized JSON text plus a plain count dict,
    so no array object of any kind crosses the queue. Worker dispatch
    counts merge into the parent counter, keeping the dispatch CI gate
    observable per shard."""

    def __init__(self, n: int = 2):
        if n < 1:
            raise ValueError(f"processes executor needs n >= 1, got {n}")
        self.n = n

    def execute(self, labels, cfgs, data, *, stack):
        import multiprocessing as mp

        refuse_child_interpreters(f"processes:n={self.n}")
        shards = [s for s in partition_runs(cfgs, self.n) if s]
        tasks = []
        for idxs in shards:
            task = ([labels[i] for i in idxs], [cfgs[i] for i in idxs],
                    data, stack)
            assert_host_only(task, where="shard task")
            tasks.append(task)
        if not shards:
            return []
        # always a real pool — even for one shard — so the isolation
        # contract (worker-local jit/eval caches, host-only queue traffic)
        # does not silently depend on the shard count
        ctx = mp.get_context("spawn")
        with ctx.Pool(processes=min(self.n, len(shards))) as pool:
            outs = pool.map(_worker_run_shard, tasks)
        return merge_shard_payloads(len(cfgs), shards, outs)


# ---------------------------------------------------------------------------
# executor registry (shared spec grammar: "devices:n=8", "processes:n=2",
# "hosts:channel=local,n=4,retries=2")
# ---------------------------------------------------------------------------

def _hosts_factory(**params) -> SweepExecutor:
    """``"hosts:channel=...,n=K,retries=R"``: the multi-host launcher
    (:mod:`repro.core.launcher`) — shards dispatched to independent host
    processes through a pluggable ``HostChannel`` (``local`` subprocesses,
    ``ssh`` remotes, ``slurm`` array jobs) with shard-level retry.
    Imported lazily: the launcher builds on this module."""
    from repro.core.launcher import HostsExecutor
    return HostsExecutor(**params)


EXECUTORS: Dict[str, Callable[..., SweepExecutor]] = {
    "none": _SequentialExecutor,
    "devices": _DeviceShardExecutor,
    "processes": _ProcessShardExecutor,
    "hosts": _hosts_factory,
}

_EXECUTOR_CACHE: Dict[str, SweepExecutor] = {}


def register_executor(name: str,
                      factory: Callable[..., SweepExecutor]) -> None:
    """Register a sweep-executor factory under a spec name."""
    register_factory(EXECUTORS, name, factory, "sweep executor")


def get_executor(spec: str) -> SweepExecutor:
    """Resolve an executor spec string (``"none"``, ``"devices:n=8"``,
    ``"processes:n=2"``) to a cached executor; :class:`KeyError` on
    unknown names / malformed specs, :class:`ValueError` on bad ``n``."""
    return resolve_spec(spec, EXECUTORS, _EXECUTOR_CACHE, "sweep executor")
