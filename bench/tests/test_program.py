"""The reduction of the program's own spans (``bench/program.py``): on a
hand-made trace with nested ``htl.*`` spans on two threads, on the
recorded trace that has none, and on a real profiler trace of the CPU."""
import glob
import json
import os

import pytest

from bench import program, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "city_trace_cut.json")


def _scenario(thread, t0, n):
    """One scenario's spans from ``t0``: sweep > scenario > the six steps,
    with ``n`` scaling the counts."""
    steps = [("htl.plan", 10, 70, {"windows": 10, "dcs": 3 * n,
                                   "events": 5 * n}),
             ("htl.pack", 80, 60, {"slots": 100 * n, "rows": 10 * n,
                                   "bytes": 4000 * n}),
             ("htl.upload", 140, 20, {"bytes": 4000 * n}),
             ("htl.dispatch", 160, 10, {"entry": "scan_windows"}),
             ("htl.fetch", 170, 160, {}),
             ("htl.result", 330, 20, {})]
    return ([["htl.sweep", t0 - 10, 380, thread, {"rows": 1}],
             ["htl.scenario", t0, 360, thread, {"windows": 10}]]
            + [[name, t0 + s, d, thread, stats]
               for name, s, d, stats in steps])


@pytest.fixture
def flat():
    # device 0 busy [20, 50) (outside the scenarios), [285, 440) (thread
    # A's dispatch end and fetch) and [700, 900) (thread B's upload to its
    # fetch's end); two scenarios of the harness, one on each thread
    return {"devices": {"0": [["fusion.1", 20, 30, "fusion"],
                              ["while.2", 285, 155, "while"],
                              ["while.2", 700, 200, "while"]]},
            "spans": [["bench.window", 0, 1000],
                      ["bench.scenario", 100, 400],
                      ["bench.scenario", 550, 400]],
            "program": _scenario("/host:CPU/0:python", 120, 1)
            + _scenario("/host:CPU/3:worker", 560, 2)}


def test_self_time_idle_and_stats(flat):
    # thread A: sweep [110, 490), scenario [120, 480), plan [130, 200),
    # pack [200, 260), upload [260, 280), dispatch [280, 290), fetch
    # [290, 450), result [450, 470); thread B the same 440 ns later
    out = program.reduce(flat, span="bench.scenario")
    p = out["program"]
    ns = 1e-9
    assert p["htl.plan"]["self_s"] == pytest.approx(140 * ns)
    assert p["htl.plan"]["idle_s"] == pytest.approx(140 * ns)
    # A's dispatch [280, 290): busy from 285; B's [720, 730) busy
    assert p["htl.dispatch"]["idle_s"] == pytest.approx(5 * ns)
    # A's fetch [290, 450): busy to 440; B's [730, 890) all busy
    assert p["htl.fetch"]["self_s"] == pytest.approx(320 * ns)
    assert p["htl.fetch"]["idle_s"] == pytest.approx(10 * ns)
    # B's upload [700, 720) is all busy, A's [260, 280) all idle
    assert p["htl.upload"]["idle_s"] == pytest.approx(20 * ns)
    # A's result [450, 470) idle; B's [890, 910): busy to 900
    assert p["htl.result"]["idle_s"] == pytest.approx(30 * ns)
    # scenario [120, 480) less its steps [130, 470): 20 of self, idle
    assert p["htl.scenario"]["self_s"] == pytest.approx(40 * ns)
    assert p["htl.scenario"]["idle_s"] == pytest.approx(40 * ns)
    assert p["htl.sweep"]["self_s"] == pytest.approx(40 * ns)
    assert p["htl.sweep"]["idle_s"] == pytest.approx(40 * ns)
    assert p["htl.pack"]["count"] == 2
    assert p["htl.pack"]["stats"] == {"slots": 300, "rows": 30,
                                      "bytes": 12000}
    assert p["htl.plan"]["stats"] == {"windows": 20, "dcs": 9, "events": 15}
    assert p["htl.dispatch"]["stats"] == {}      # a name is no count
    # the items' edges no program span covers: [100, 110), [490, 500)
    # and [930, 950)
    assert out["unattributed_idle_s"] == pytest.approx(40 * ns)


def test_parts_add_up_to_the_host_time(flat):
    out = program.reduce(flat, span="bench.scenario")
    windows = 20
    parts = program.parts(out, windows)
    host_ms = out["span_idle_s"] * 1e3 / windows
    ms = [v for k, v in parts.items() if k.endswith("_ms_per_window")]
    assert len(ms) == 7
    assert sum(ms) == pytest.approx(host_ms, rel=1e-12)
    assert parts["launch_ms_per_window"] == pytest.approx(
        (out["program"]["htl.dispatch"]["idle_s"]
         + out["program"]["htl.fetch"]["idle_s"]) * 1e3 / windows)
    assert parts["slot_fill"] == pytest.approx(10.0)
    assert parts["upload_mb_per_window"] == pytest.approx(12000 / 1e6 / 20)


def test_gaps_are_named_by_the_innermost_span(flat):
    out = program.reduce(flat, span="bench.scenario", top=3)
    # gaps [0, 20), [50, 285), [440, 700), [900, 1000): the three longest
    # by their middles 570 (B's plan), 167 (A's plan) and 950 (past the
    # second scenario's end)
    assert out["idle_gaps"] == [
        ["htl.plan", pytest.approx(260e-9)],
        ["htl.plan", pytest.approx(235e-9)],
        ["outside harness spans", pytest.approx(100e-9)]]
    # the same gaps, named by harness spans alone
    assert [n for n, _ in trace.reduce(flat, span="bench.scenario",
                                       top=3)["idle_gaps"]] == \
        ["bench.scenario", "bench.scenario", "outside harness spans"]


def test_without_program_spans_the_reduction_is_the_harness_one():
    flat = json.load(open(DATA))
    want = trace.reduce(flat, span="bench.scenario")
    got = program.reduce(dict(flat, program=[]), span="bench.scenario")
    for key in ("busy_s", "window_s", "devices", "span_idle_s",
                "device_ops", "idle_gaps"):
        assert json.dumps(got[key]) == json.dumps(want[key]), key
    assert got["program"] == {}
    # every idle instant of the scenario spans is one no span covers
    assert got["unattributed_idle_s"] == pytest.approx(want["span_idle_s"])
    assert program.parts(got, 10) == {}


def test_program_spans_read_from_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("htl.scenario", windows=4):
            with jax.profiler.TraceAnnotation("htl.pack") as sp:
                x = jnp.ones(8).sum()
                sp.set_metadata(slots=64, rows=8)
            with jax.profiler.TraceAnnotation("bench.other"):
                float(x)
    finally:
        jax.profiler.stop_trace()
    xplane = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)[0]
    spans = program.program_spans(xplane)
    assert sorted(s[0] for s in spans) == ["htl.pack", "htl.scenario"]
    sc, pk = sorted(spans, key=lambda s: s[1])
    assert sc[3] == pk[3] and sc[3].startswith("/host:")
    assert sc[1] <= pk[1] and pk[1] + pk[2] <= sc[1] + sc[2]
    assert sc[4] == {"windows": 4} and pk[4] == {"slots": 64, "rows": 8}
    json.dumps(spans)
