"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
time per device operation and per kernel, and the longest idle gaps.

The benchmark marks the traced window with a host span ``bench.window``
and its own calls with further ``bench.*`` spans (``jax.profiler``
annotations share the device's clock).  Device operations are the
events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.
"""

from __future__ import annotations

import bisect
import gzip
import dataclasses
import re

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
# a TPU op event is named by its HLO instruction, "%name.N = type op(...)"
_OP = re.compile(r"%?([^\s=%]+?)(?:\.\d+)?(?:\s|=|$)")
# ops whose events hold other ops' events (a scan's loop): they count
# toward busy time but not toward any op's own time
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``"%apmm_fused_linear.45 = bf16[16,2560] custom-call(..)"`` ->
    ``"apmm_fused_linear"``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Reduction:
    window_s: float                  # length of the traced window
    busy_s: float                    # union of device-op time, per chip
    n_devices: int
    op_s: dict                       # op name (``op_name``) -> seconds
    gaps: list                       # [(host span, seconds)] longest first

    def kernel_s(self, names) -> float | None:
        """Device seconds of the ops named ``names`` (``op_name``); None
        when none ran."""
        hits = [s for n, s in self.op_s.items() if n in names]
        return sum(hits) if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_profile(planes) -> Reduction:
    """``planes``: iterable of ``(plane name, [(line name, [(event name,
    start ns, duration ns)])])`` -- the shape of ``ProfileData``."""
    host_spans, device_ops = [], {}
    for pname, lines in planes:
        is_dev = pname.startswith("/device:TPU:")
        for lname, events in lines:
            if is_dev and lname == "XLA Ops":
                device_ops.setdefault(pname, []).extend(events)
            elif not is_dev:
                host_spans.extend(e for e in events
                                  if e[0].startswith(HOST_PREFIX))
    wins = [e for e in host_spans if e[0] == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"trace holds {len(wins)} {WINDOW_SPAN!r} spans, "
                         f"want 1")
    if not device_ops:
        raise ValueError("trace holds no device operations")
    w0 = wins[0][1]
    w1 = w0 + wins[0][2]
    op_s, busy, gaps = {}, 0.0, []
    segments = _Segments(w0, w1, [e for e in host_spans
                                  if e[0] != WINDOW_SPAN])
    for pname, events in device_ops.items():
        iv = []
        for name, start, dur in events:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            iv.append((a, b))
            key = op_name(name)
            if key not in CONTAINERS:
                op_s[key] = op_s.get(key, 0.0) + (b - a) * 1e-9
        merged = _union(iv)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps += segments.attribute(a, b)
    n = len(device_ops)
    for k in op_s:
        op_s[k] /= n
    by_span: dict = {}
    for span, s in gaps:
        by_span[span] = by_span.get(span, 0.0) + s / n
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy / n,
                     n_devices=n, op_s=op_s,
                     gaps=sorted(by_span.items(), key=lambda kv: -kv[1]))


class _Segments:
    """The window cut at every host span edge, each piece labelled with
    the innermost benchmark span open over it."""

    def __init__(self, w0, w1, spans):
        cuts = sorted({w0, w1} | {t for _, s, d in spans
                                  for t in (s, s + d) if w0 < t < w1})
        self.cuts = cuts
        self.labels = [_span_at((x + y) / 2, spans)
                       for x, y in zip(cuts, cuts[1:])]

    def attribute(self, a, b) -> list:
        """``[(span, seconds)]`` of the idle interval ``[a, b]``."""
        i = max(bisect.bisect_right(self.cuts, a) - 1, 0)
        out = []
        while i < len(self.labels) and self.cuts[i] < b:
            x, y = max(self.cuts[i], a), min(self.cuts[i + 1], b)
            if y > x:
                out.append((self.labels[i], (y - x) * 1e-9))
            i += 1
        return out


def _span_at(t, spans) -> str:
    """Innermost benchmark host span open at ``t`` (else the window)."""
    best = None
    for name, start, dur in spans:
        if start <= t <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else WINDOW_SPAN


def read_xplane(path) -> Reduction:
    """Reduce the ``.xplane.pb`` (or gzipped ``.xplane.pb.gz``) file at
    ``path``."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    planes = [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events])
                        for ln in p.lines])
              for p in data.planes]
    return reduce_profile(planes)
