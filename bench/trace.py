"""Device trace: recording, and its reduction to busy time, idle gaps and
the breakdown.

The reduction works on time windows and operation categories, never on
the names of jitted programs. A trace is first flattened into a small
JSON-safe form (:func:`flatten`):

    {"devices": {"0": [[op name, start ns, duration ns, category], ...]},
     "spans":   [[span name, start ns, duration ns], ...]}

``devices`` holds every operation of each TPU's ``XLA Ops`` line;
``spans`` holds the host spans the harness wrote with
``jax.profiler.TraceAnnotation`` (names starting ``bench.``). Both sit on
the profiler's one clock. :func:`reduce` then reads everything from that
form, so the tests check it on a recorded trace cut to a few hundred
events.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

class Recorder:
    """Profiler session around the measured window: ``start`` before it,
    ``stop`` after; the xplane file lands in a temporary directory that
    :meth:`close` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> str:
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.dir}")
        return found[0]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


OP = re.compile(r"^%?([^ =]+) = .*? ([a-z][a-z0-9\-]*)\(")


def op_name(text: str) -> Tuple[str, str]:
    """(name, opcode) of an ``XLA Ops`` event, whose name is the HLO
    instruction's text (``%fusion.3 = f32[...] fusion(...)``)."""
    m = OP.match(text)
    if m:
        return m.group(1), m.group(2)
    return text.split(" ", 1)[0].lstrip("%"), ""


def trimmed(flat: dict, ops: int) -> dict:
    """The first ``ops`` operations of each device, and the spans."""
    return {"devices": {d: v[:ops] for d, v in flat["devices"].items()},
            "spans": flat["spans"]}


def flatten(xplane: str) -> dict:
    """The trace's TPU operations and harness spans (module doc); an
    operation's category is its HLO opcode."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, opcode = op_name(e.name)
                    ops.append([name, int(e.start_ns), int(e.duration_ns),
                                opcode])
            devices[m.group(1)] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
    return {"devices": devices, "spans": spans}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Interval], lo: int, hi: int
          ) -> List[Interval]:
    """Merged, clipped-to-[lo, hi) union of half-open intervals."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi)
    out: List[Interval] = []
    for a, b in clipped:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


class Coverage:
    """``cover(lo, hi)``: the length of [lo, hi) that merged (sorted,
    disjoint) intervals cover, in O(log n)."""

    def __init__(self, merged: Sequence[Interval]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.prefix = [0]
        for a, b in merged:
            self.prefix.append(self.prefix[-1] + (b - a))

    def _upto(self, t: int) -> int:
        """Covered length of (-inf, t)."""
        i = bisect.bisect_right(self.starts, t)   # intervals starting <= t
        if i == 0:
            return 0
        return self.prefix[i - 1] + min(self.ends[i - 1], t) \
            - self.starts[i - 1]

    def __call__(self, lo: int, hi: int) -> int:
        return max(0, self._upto(hi) - self._upto(lo)) if hi > lo else 0


def gaps(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, cur = [], lo
    for a, b in merged:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def window_of(flat: dict) -> Interval:
    wins = [(s, s + d) for n, s, d in flat["spans"] if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return wins[0]


def innermost(spans: Sequence[list], t: int) -> str:
    """Name of the shortest harness span, other than the window, that
    holds instant ``t``."""
    best, best_len = "outside harness spans", None
    for name, s, d in spans:
        if name == WINDOW_SPAN or not s <= t < s + d:
            continue
        if best_len is None or d < best_len:
            best, best_len = name, d
    return best


def reduce(flat: dict, *, span: Optional[str] = None, top: int = 10
           ) -> dict:
    """Numbers of one traced window.

    * ``busy_s``: the union of each TPU's operation intervals inside the
      window, averaged over the TPUs;
    * ``window_s``: the window span's length;
    * ``span_idle_s``: device 0's idle time inside the spans named
      ``span`` (the harness's per-item spans);
    * ``device_ops``: device 0's operation time by operation name, the
      ``top`` largest;
    * ``idle_gaps``: device 0's ``top`` longest idle gaps, each named by
      the innermost harness span that holds its middle."""
    lo, hi = window_of(flat)
    devs = sorted(flat["devices"], key=int)
    if not devs:
        raise ValueError("the trace holds no TPU operations")
    merged = {d: union([(s, s + du) for _, s, du, _ in flat["devices"][d]],
                       lo, hi) for d in devs}
    busy = [sum(b - a for a, b in merged[d]) for d in devs]
    d0 = devs[0]
    by_name: Dict[str, int] = {}
    for name, s, du, _ in flat["devices"][d0]:
        if s >= hi or s + du <= lo:
            continue
        by_name[name] = by_name.get(name, 0) + du
    longest = heapq.nlargest(top, gaps(merged[d0], lo, hi),
                             key=lambda g: g[1] - g[0])
    cover = Coverage(merged[d0])
    span_idle = None
    if span is not None:
        items = [(max(s, lo), min(s + d, hi)) for n, s, d in flat["spans"]
                 if n == span and s + d > lo and s < hi]
        span_idle = sum((b - a) - cover(a, b) for a, b in items) / 1e9
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(devs),
        "span_idle_s": span_idle,
        "device_ops": [[n, t / 1e9] for n, t in
                       heapq.nlargest(top, by_name.items(),
                                      key=lambda kv: kv[1])],
        "idle_gaps": [[innermost(flat["spans"], (a + b) // 2), (b - a) / 1e9]
                      for a, b in longest],
    }
