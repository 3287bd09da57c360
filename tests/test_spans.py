"""Host spans of the scan engine and the sweep layer (repro.core.dispatch.span)
under a real ``jax.profiler`` trace: every ``htl.*`` span is written,
nested in the order the work runs, with counts equal to the same numbers
recomputed from the planner's and packer's outputs; and a traced sweep
gives byte-for-byte the result of an untraced one."""
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core.cityscan import _pack_plan, _plan_scenario
from repro.core.dispatch import SPAN_PREFIX, dispatch_scope
from repro.core.experiment import get_preset
from repro.data.synthetic_covtype import make_covtype_like

DATA = make_covtype_like(seed=0, n_total=3000)
STEPS = ("htl.plan", "htl.pack", "htl.upload", "htl.dispatch", "htl.fetch",
         "htl.result")


def _spec():
    """The smoke grid on the scan engine, HTL rows only."""
    return get_preset("smoke", windows=3, n_seeds=1, engine="scan")


def _host_spans(xplane):
    """[(name, start, end, thread, stats)] of the ``htl.*`` host events."""
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, (plane.name, i),
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(spans, traced SweepResult, untraced SweepResult, dispatch delta)."""
    spec = _spec()
    untraced = spec.run(DATA)            # also compiles every shape
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    try:
        with dispatch_scope() as delta:
            result = spec.run(DATA)
    finally:
        jax.profiler.stop_trace()
    xplane = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                       recursive=True)
    assert xplane, "the profiler wrote no trace"
    return _host_spans(xplane[0]), result, untraced, delta


def _inside(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_every_span_is_written_and_nested_in_order(traced):
    spans, result, _, _ = traced
    rows = len(result.records)
    sweeps = [s for s in spans if s[0] == "htl.sweep"]
    assert len(sweeps) == 1
    assert sweeps[0][4]["rows"] == rows
    scenarios = [s for s in spans if s[0] == "htl.scenario"]
    assert len(scenarios) == rows
    for sc in scenarios:
        assert _inside(sc, sweeps[0])
        assert sc[4]["windows"] == 3
        kids = [s for s in spans if s[0] in STEPS and _inside(s, sc)]
        assert [k[0] for k in kids] == list(STEPS)
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], (a[0], b[0])
        assert kids[STEPS.index("htl.dispatch")][4]["entry"] == \
            "scan_windows"


def test_counts_equal_the_planner_and_packer_outputs(traced):
    spans, result, _, _ = traced
    plan = [s for s in spans if s[0] == "htl.plan"]
    pack = [s for s in spans if s[0] == "htl.pack"]
    upload = [s for s in spans if s[0] == "htl.upload"]
    cfgs = [cfg for _, cfg in _spec().configs()]
    assert len(plan) == len(pack) == len(upload) == len(cfgs)
    for cfg, pl, pk, up in zip(cfgs, plan, pack, upload):
        plans, ledger = _plan_scenario(cfg, DATA)
        packed = _pack_plan(cfg, plans)
        assert pl[4] == {"windows": cfg.windows,
                         "dcs": sum(len(p.live) for p in plans),
                         "events": len(ledger.events)}
        nbytes = sum(a.nbytes for a in packed.values())
        assert pk[4] == {"slots": packed["mb"].size + packed["mr"].size,
                         "rows": int(packed["mb"].sum()
                                     + packed["mr"].sum()),
                         "bytes": nbytes}
        assert 0 < pk[4]["rows"] < pk[4]["slots"]
        assert up[4] == {"bytes": nbytes}


def test_trace_changes_no_result_and_no_dispatch_count(traced):
    _, result, untraced, delta = traced
    assert result.to_json() == untraced.to_json()
    assert delta == {"scan_windows": len(result.records)}
    assert np.isfinite([v for r in result.records for v in r.f1_curve]).all()
