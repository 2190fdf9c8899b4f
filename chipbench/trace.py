"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From each TPU chip's plane: the op events (line ``XLA Ops``) and the
program events (line ``XLA Modules``), each op tagged with the program
whose event holds it.  From the host: the benchmark's own spans
(``jax.profiler.TraceAnnotation`` named ``chipbench.*``).  The window is
the host span ``chipbench.window``; everything is clipped to it.

Intervals are (start_ns, end_ns) on the trace's one clock.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")
_COLL = re.compile(r"^(%s)(-start|-done)?(\b|[._-])" % "|".join(COLLECTIVES))


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float
    module: str = ""


@dataclasses.dataclass
class Reduced:
    window: tuple                 # (start, end)
    chips: list                   # per chip: [Op] clipped to the window
    spans: list                   # [(name, start, end)] host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def module_name(raw: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", raw).strip()


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _tag_modules(ops, modules):
    """Give each op the name of the program event that holds it."""
    modules = sorted(modules, key=lambda m: m[1])
    j = 0
    for op in sorted(ops, key=lambda o: o.start):
        while j < len(modules) and modules[j][2] < op.start:
            j += 1
        if j < len(modules) and modules[j][1] <= op.start <= modules[j][2]:
            op.module = module_name(modules[j][0])
    return ops


def clip(iv, window):
    s, e = max(iv[0], window[0]), min(iv[1], window[1])
    return (s, e) if e > s else None


def from_events(chips_events, spans, window=None) -> Reduced:
    """Build a :class:`Reduced` from plain lists: ``chips_events`` holds per
    chip ``(ops, modules)``, each a list of (name, start, end)."""
    if window is None:
        ws = [s for s in spans if s[0] == WINDOW]
        if not ws:
            raise ValueError(f"no {WINDOW!r} span in the trace")
        window = (ws[0][1], ws[0][2])
    chips = []
    for ops, modules in chips_events:
        tagged = _tag_modules([Op(n, s, e) for n, s, e in ops], modules)
        kept = []
        for op in tagged:
            c = clip((op.start, op.end), window)
            if c:
                kept.append(Op(op.name, c[0], c[1], op.module))
        chips.append(kept)
    return Reduced(window=window, chips=chips, spans=list(spans))


def reduce(path: str, host_ops_as_chip: bool = False) -> Reduced:
    """The trace at ``path``, reduced; raises ValueError where it holds no
    TPU plane.  ``host_ops_as_chip`` lets the host's XLA op events stand
    in for one chip instead: only the CPU tests pass it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, spans, cpu_ops = [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = _events(lines[MODULES_LINE]) \
                if MODULES_LINE in lines else []
            chips.append((int(plane.name.rsplit(":", 1)[1]), ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
                    elif "hlo_module" in dict(e.stats):
                        cpu_ops.append(e)
    chips.sort()
    if not chips:
        if not (host_ops_as_chip and cpu_ops):
            raise ValueError(f"no /device:TPU:n plane in {path} (planes "
                             f"{[p.name for p in pd.planes]})")
        ops = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
               for e in cpu_ops]
        mods = [(dict(e.stats)["hlo_module"], float(e.start_ns),
                 float(e.start_ns + e.duration_ns)) for e in cpu_ops]
        chips = [(0, ops, mods)]
    return from_events([(o, m) for _, o, m in chips], spans)


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list:
    """Intersection of two unions (sorted, disjoint)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def busy(ops) -> list:
    return union((o.start, o.end) for o in ops)


def busy_s(red: Reduced) -> float:
    """Seconds in which some op ran, averaged over the chips."""
    return sum(total(busy(c)) for c in red.chips) / len(red.chips) * 1e-9


def idle_share(red: Reduced) -> float:
    return 1.0 - busy_s(red) / red.window_s


def is_collective(name: str) -> bool:
    return bool(_COLL.match(name.replace("_", "-")))


def collective_intervals(ops) -> list:
    """Collective ops as intervals; an async ``X-start`` ... ``X-done``
    pair counts from the start's beginning to the done's end (each done
    closes the oldest open start of its kind)."""
    out, open_ = [], {}
    for op in sorted(ops, key=lambda o: o.start):
        m = _COLL.match(op.name.replace("_", "-"))
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_.setdefault(kind, []).append(op.start)
        elif phase == "-done" and open_.get(kind):
            out.append((open_[kind].pop(0), op.end))
        else:
            out.append((op.start, op.end))
    return out


def collective_s(red: Reduced) -> tuple:
    """(collective, exposed) seconds averaged over chips: the union of the
    collective intervals, and the part of it in which no other op runs."""
    coll = exposed = 0.0
    for ops in red.chips:
        c = union(collective_intervals(ops))
        other = busy([o for o in ops if not is_collective(o.name)])
        coll += total(c)
        exposed += total(c) - total(intersect(c, other))
    n = len(red.chips)
    return coll / n * 1e-9, exposed / n * 1e-9


def module_s(red: Reduced, pattern: str) -> float:
    """Busy seconds of the ops of programs whose name matches
    ``pattern``, averaged over the chips."""
    rx = re.compile(pattern)
    return sum(total(busy([o for o in ops if rx.search(o.module)]))
               for ops in red.chips) / len(red.chips) * 1e-9


def spans_named(red: Reduced, name: str) -> list:
    return [(s, e) for n, s, e in red.spans
            if n == name and clip((s, e), red.window)]


def device_s_within(red: Reduced, spans) -> float:
    """Busy seconds of the chips inside the given host spans, averaged."""
    sp = union(spans)
    return sum(total(intersect(busy(ops), sp)) for ops in red.chips) \
        / len(red.chips) * 1e-9


def top_ops(red: Reduced, n: int = 10) -> list:
    """[name, seconds]: the ops that took most device time (by name,
    summed, averaged over chips)."""
    acc = {}
    for ops in red.chips:
        for o in ops:
            acc[o.name] = acc.get(o.name, 0.0) + (o.end - o.start)
    k = len(red.chips)
    return [[name, t / k * 1e-9] for name, t in
            sorted(acc.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(red: Reduced, n: int = 10) -> list:
    """[name, seconds]: the longest gaps with no op on chip 0, each named
    by the innermost benchmark span that covers most of it."""
    b = busy(red.chips[0]) if red.chips else []
    edges = [red.window[0]] + [x for iv in b for x in iv] + [red.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g in gaps[:n]:
        out.append([_covering_span(red.spans, g), (g[1] - g[0]) * 1e-9])
    return out


def _covering_span(spans, gap) -> str:
    best, best_key = "none", None
    for name, s, e in spans:
        if name == WINDOW:
            continue
        c = clip((s, e), gap)
        if c is None:
            continue
        key = (c[1] - c[0], -(e - s))      # most overlap, then innermost
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def breakdown(red: Reduced) -> dict:
    return {"device_ops": top_ops(red), "idle_gaps": idle_gaps(red)}


def summary(path: str, per_line: int = 3) -> str:
    """Planes, lines and a few events of a trace, for a look by hand."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            out.append(f"  line {ln.name!r}: {len(evs)} events")
            for e in evs[:per_line]:
                out.append(f"    {e.name!r} {e.start_ns} +{e.duration_ns} "
                           f"{dict(e.stats)}")
    return "\n".join(out)


def find_xplane(log_dir: str) -> Optional[str]:
    import glob
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None
