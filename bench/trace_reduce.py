"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-op device time and idle gaps.

- The window is the host span ``bench.window`` that the harness opens
  around its measured loop.
- Device ops are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane. Busy time is the union of their intervals
  inside the window, per chip, averaged over the chips. A control-flow op
  (a ``while`` or ``conditional``) spans the ops of its body on the same
  line, so it counts in busy time through the union only, and is left out
  of the time by op.
- An idle gap is an interval of the window in which no op runs on a chip.
  Each gap is named by the innermost ``bench.*`` host span that covers its
  middle (``bench.window`` when no deeper one does).
- Collective time is the time of collective ops on a chip during which
  none of its other ops runs.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"
CONTROL = ("while", "conditional")
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals):
    """Merge [(start, end)] into sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Disjoint sorted intervals ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Op:
    name: str
    start: int          # ns
    end: int
    chip: int
    hlo_module: str = ""
    detail: str = ""    # every stat of the op and its metadata, as text
    category: str = ""  # the op's ``hlo_category`` stat
    source: str = ""    # the op's ``source`` stat: file and line

    @property
    def short(self) -> str:
        """The HLO instruction's own name (``fusion.13``), where the name
        is the instruction's whole text."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def label(self) -> str:
        """The short name, with the category and the source line."""
        where = self.source.split("/src/", 1)[-1]
        parts = [p for p in (self.category, where) if p]
        return f"{self.short} ({', '.join(parts)})" if parts else self.short

    @property
    def is_collective(self) -> bool:
        return any(c in self.short for c in COLLECTIVES)

    @property
    def is_kernel(self) -> bool:
        """A Pallas kernel: a TPU custom call. It is named by the JAX call
        that made it (e.g. ``jvp__.8``), so it is found by its HLO text,
        which a v5e trace gives as the op's name and another trace may give
        in a stat; "pallas_call" is no mark, since the text of an op that
        reads a kernel's output names that output."""
        return ("tpu_custom_call" in self.name
                or "tpu_custom_call" in self.detail)

    @property
    def is_control(self) -> bool:
        """A while or conditional, whose interval holds its body's ops."""
        return self.category in CONTROL


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # union busy, averaged over chips
    busy_by_chip: list
    ops: list = field(default_factory=list)       # [Op] inside the window
    gaps: list = field(default_factory=list)      # [(seconds, span name)]
    collective_exposed_s: list = field(default_factory=list)  # per chip

    def op_seconds(self, match) -> float:
        """Device seconds of ops whose name satisfies ``match``, summed over
        chips and divided by the chip count."""
        n = max(1, len(self.busy_by_chip))
        return sum(o.end - o.start for o in self.ops if match(o)) * 1e-9 / n

    def top_ops(self, n: int):
        by = {}
        for o in self.ops:
            if o.is_control:
                continue
            by[o.label] = by.get(o.label, 0) + (o.end - o.start)
        chips = max(1, len(self.busy_by_chip))
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / chips] for k, v in top]

    def top_gaps(self, n: int):
        return [[name, s] for s, name in sorted(self.gaps,
                                                key=lambda g: -g[0])[:n]]


# ---------------------------------------------------------------------------
# The stats of an op's metadata (its HLO text, category, module) are not
# exposed by ``jax.profiler.ProfileData``, which gives an event's own stats
# only; they are read from the serialized XSpace here, by its field numbers
# (tsl/profiler/protobuf/xplane.proto).
# ---------------------------------------------------------------------------

_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MD_NAME, _MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_INTS = (3, 4)


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of a message in ``buf[start:end]``: an int for
    a varint, (start, end) of the bytes of a length-delimited field."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """The value of one map<int64, message> entry, as (start, end)."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return (span[1], span[1])


def metadata_stats(path: str, prefix: str = "/device:") -> dict:
    """{plane name: {op name: {stat name: text}}} of the event metadata of
    the planes whose name starts with ``prefix``."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        name, ev_md, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == _PLANE_NAME:
                name = _text(buf, v)
            elif g == _PLANE_EVENT_MD:
                ev_md.append(_map_values(buf, v))
            elif g == _PLANE_STAT_MD:
                sid, sname = 0, ""
                for h, w in _fields(buf, *_map_values(buf, v)):
                    if h == 1:
                        sid = w
                    elif h == 2:
                        sname = _text(buf, w)
                stat_names[sid] = sname
        if not name.startswith(prefix):
            continue
        ops = out.setdefault(name, {})
        for md in ev_md:
            op, stats = "", {}
            for g, v in _fields(buf, *md):
                if g == _MD_NAME:
                    op = _text(buf, v)
                elif g == _MD_STATS:
                    sid, val = 0, ""
                    for h, w in _fields(buf, *v):
                        if h == _STAT_MD_ID:
                            sid = w
                        elif h == _STAT_STR:
                            val = _text(buf, w)
                        elif h == _STAT_REF:
                            val = stat_names.get(w, "")
                        elif h in _STAT_INTS:
                            val = str(w)
                    stats[stat_names.get(sid, str(sid))] = val
            if op:
                ops[op] = stats
    return out


def _containing(intervals, starts, t) -> str:
    """The name of the interval of sorted, disjoint ``intervals``
    ([(start, end, name)]) that holds ``t``; "" where none does."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < intervals[i][1]:
        return intervals[i][2]
    return ""


def reduce(path: str, n_chips: int) -> Summary:
    """Busy time, ops, idle gaps and exposed collectives of the first
    ``n_chips`` chips in the last ``bench.window`` of the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    md = metadata_stats(path)
    spans = []          # (start, end, name) of bench.* host spans
    by_chip = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            if chip >= n_chips:
                continue
            plane_md = md.get(plane.name, {})
            lines = {line.name: line for line in plane.lines}
            modules = sorted((int(e.start_ns), int(e.end_ns), e.name)
                             for e in (lines["XLA Modules"].events
                                       if "XLA Modules" in lines else ()))
            module_starts = [m[0] for m in modules]
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines
                      else ()):
                stats = dict(plane_md.get(e.name, {}))
                stats.update((k, str(v)) for k, v in e.stats)
                detail = " ".join(stats.values())
                start = int(e.start_ns)
                module = (_containing(modules, module_starts, start)
                          or stats.get("hlo_module", ""))
                by_chip.setdefault(chip, []).append(Op(
                    e.name, start, int(e.end_ns), chip, module, detail,
                    stats.get("hlo_category", ""), stats.get("source", "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((int(e.start_ns), int(e.end_ns),
                                      e.name))
    windows = [s for s in spans if s[2] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = windows[-1][0], windows[-1][1]
    inner = sorted(s for s in spans if s[2] != WINDOW)
    starts = [s[0] for s in inner]

    def span_at(t):
        # nested spans: the covering one that starts last is innermost
        for s, e, name in reversed(inner[:bisect.bisect_right(starts, t)]):
            if t < e:
                return name
        return WINDOW

    ops, busy, gaps, exposed = [], [], [], []
    for chip in range(n_chips):
        chip_ops = [o for o in by_chip.get(chip, []) if o.end > w0
                    and o.start < w1]
        ops.extend(chip_ops)
        ivs = union(clip([(o.start, o.end) for o in chip_ops], w0, w1))
        busy.append(total(ivs) * 1e-9)
        for s, e in subtract([(w0, w1)], ivs):
            gaps.append(((e - s) * 1e-9, span_at((s + e) // 2)))
        coll = union(clip([(o.start, o.end) for o in chip_ops
                           if o.is_collective], w0, w1))
        other = union(clip([(o.start, o.end) for o in chip_ops
                            if not (o.is_collective or o.is_control)],
                           w0, w1))
        exposed.append(total(subtract(coll, other)) * 1e-9)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / max(1, n_chips), busy_by_chip=busy,
                   ops=ops, gaps=gaps, collective_exposed_s=exposed)
