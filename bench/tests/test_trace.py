"""The trace reduction, checked on a trace recorded on the chip and cut to
62 operations, and on small hand-made ones."""
import json
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "city_trace_cut.json")


def _timeline(flat, device="0"):
    """Brute force: one boolean per nanosecond of the window."""
    lo, hi = trace.window_of(flat)
    busy = np.zeros(hi - lo, bool)
    for _, s, d, _ in flat["devices"][device]:
        busy[max(s, lo) - lo:max(min(s + d, hi) - lo, 0)] = True
    return lo, hi, busy


def test_recorded_trace_busy_and_gaps():
    flat = json.load(open(DATA))
    lo, hi, busy = _timeline(flat)
    out = trace.reduce(flat, span="bench.scenario")
    assert out["window_s"] == (hi - lo) / 1e9
    assert out["busy_s"] == busy.sum() / 1e9
    # the idle gaps, brute force: runs of False
    edges = np.flatnonzero(np.diff(np.concatenate([[1], busy, [1]])
                                   .astype(int)))
    runs = sorted((b - a for a, b in zip(edges[::2], edges[1::2])),
                  reverse=True)
    assert [g for _, g in out["idle_gaps"]] == [r / 1e9 for r in runs[:10]]
    # the longest gap is the host's work at a scenario's end (fetch, F1,
    # ledger): ~4.7 ms inside the first scenario's span; the 77 us between
    # the two spans is outside both
    s1, s2 = [s for s in flat["spans"] if s[0] == "bench.scenario"]
    assert s1[1] + s1[2] < s2[1]
    assert out["idle_gaps"][0][0] == "bench.scenario"
    assert 4e-3 < out["idle_gaps"][0][1] < 6e-3
    assert trace.innermost(flat["spans"], s1[1] + s1[2] + 10) == \
        "outside harness spans"
    # idle inside the scenario spans = idle of the window minus the gap
    # between them
    inside = sum(not b for b in busy[max(s1[1], lo) - lo:
                                     min(s1[1] + s1[2], hi) - lo]) + sum(
        not b for b in busy[max(s2[1], lo) - lo:min(s2[1] + s2[2], hi)
                            - lo])
    assert out["span_idle_s"] == inside / 1e9
    assert out["devices"] == 1


def test_hand_made_union_gaps_and_spans():
    flat = {"devices": {"0": [["a", 10, 10, "fusion"],
                              ["b", 15, 10, "copy"],
                              ["c", 40, 5, "copy"],
                              ["d", 95, 20, "fusion"]],
                        "1": [["a", 0, 30, "fusion"]]},
            "spans": [["bench.window", 5, 100],
                      ["bench.scenario", 5, 40],
                      ["bench.scenario", 50, 40]]}
    out = trace.reduce(flat, span="bench.scenario", top=2)
    # device 0 in [5, 105): [10, 25) + [40, 45) + [95, 105) = 30
    # device 1 in [5, 105): [5, 30) = 25; the mean is 27.5
    assert out["busy_s"] == 27.5e-9
    assert out["window_s"] == 100e-9
    assert out["devices"] == 2
    # idle in [5, 45): 5 + 15 = 20; in [50, 90): 40
    assert out["span_idle_s"] == 60e-9
    # gaps [5,10) [25,40) [45,95): the two longest, named by their middle
    assert out["idle_gaps"] == [["bench.scenario", 50e-9],
                                ["bench.scenario", 15e-9]]
    assert out["device_ops"] == [["d", 20e-9], ["a", 10e-9]]


@pytest.mark.parametrize("text,name,opcode", [
    ("%fusion.336 = f32[250016,55,7]{2,1,0:T(8,128)} fusion(f32[1] %p), "
     "kind=kLoop", "fusion.336", "fusion"),
    ("%copy-start.1 = (s32[15384]{0:T(1024)S(1)}, s32[15384]{0:T(1024)}, "
     "u32[]{:S(2)}) copy-start(s32[15384]{0:T(1024)} %p)", "copy-start.1",
     "copy-start"),
    ("%all-gather.2 = f32[4]{0} all-gather(f32[1]{0} %x), dimensions={0}",
     "all-gather.2", "all-gather"),
    ("%all-reduce-start.7 = f32[16,55,7]{2,1,0} all-reduce-start(f32[16,55,"
     "7]{2,1,0} %s), to_apply=%add", "all-reduce-start.7",
     "all-reduce-start"),
    ("while.67", "while.67", "")])
def test_op_names(text, name, opcode):
    assert trace.op_name(text) == (name, opcode)


def test_window_is_required():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {"0": []}, "spans": []})
