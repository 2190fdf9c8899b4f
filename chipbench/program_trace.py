"""Reduce the program's own tracing in a profiler trace (``.xplane.pb``).

The serving engine writes host spans (``serve.admit``, ``serve.decode``,
``serve.dispatch``, ``serve.fetch``, ``serve.sample``, ``serve.splice``,
``host.gc``; ``repro.serve.engine``) and the model marks its ops with
``jax.named_scope`` (``attn``, ``kv_write`` inside it, ``mlp``;
``repro.models.transformer``).  This module reads both beside what
``trace.py`` reads, on the same clock:

  spans   the benchmark's ``chipbench.*`` and the program's ``serve.*`` /
          ``host.*`` host spans;
  scope   each device op's innermost model scope, from the HLO
          ``op_name`` of the op's instruction in the compiled program's
          text (``hlo_scopes``; a v5e op event carries no op_name of its
          own, only the instruction's text as its name); ``""`` where it
          has none;
  leaf    an op event that holds no other op event on its chip, so a
          ``while`` does not count its own body again.

From these: the decode's leaf device time per call by scope
(``scope_ms``), the host time per engine span (``host_ms``), and the
longest idle gaps named by the innermost span that covers them
(``idle_gaps``).  No metric of ``BENCHMARK.json`` reads them yet.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from chipbench import trace as T

PROGRAM_SPAN_PREFIXES = ("serve.", "host.")
SCOPES = ("attn", "kv_write", "mlp")
UNSCOPED = ""
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class ScopedOp(T.Op):
    scope: str = UNSCOPED
    leaf: bool = True


def scope_of(op_name: str) -> str:
    """The innermost model scope in an HLO ``op_name`` path
    (``jit(_decode)/while/body/closed_call/attn/kv_write/select_n`` ->
    ``kv_write``), or ``""``."""
    found = UNSCOPED
    for part in op_name.split("/"):
        if part in SCOPES:
            found = part
    return found


def hlo_scopes(text: str) -> dict:
    """{instruction name: scope} over a compiled program's text
    (``compiled.as_text()``), every computation, fused ones too."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1)) if op else UNSCOPED
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction an op event runs: the event's name up to its
    first space or ``=`` (a TPU plane may name an event by its whole
    instruction text)."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def mark_leaves(ops) -> list:
    """Set ``leaf`` on each op: False where another op of the same chip
    lies inside it (equal intervals hold each other neither way)."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    for i, op in enumerate(order):
        op.leaf = True
        j = i + 1
        while j < len(order) and order[j].start < op.end:
            other = order[j]
            if other.end <= op.end and (other.start, other.end) != \
                    (op.start, op.end):
                op.leaf = False
                break
            j += 1
    return ops


def from_events(chips_events, spans, window=None,
                hlo: Optional[dict] = None) -> T.Reduced:
    """As ``trace.from_events``; the ops of a program named in ``hlo``
    ({program: compiled text}) take their scope from its text."""
    if window is None:
        ws = [s for s in spans if s[0] == T.WINDOW]
        if not ws:
            raise ValueError(f"no {T.WINDOW!r} span in the trace")
        window = (ws[0][1], ws[0][2])
    maps = {prog: hlo_scopes(text) for prog, text in (hlo or {}).items()}
    chips = []
    for ops, modules in chips_events:
        made = [ScopedOp(*ev) for ev in ops]
        T._tag_modules(made, modules)
        mark_leaves(made)
        kept = []
        for op in made:
            if op.module in maps:
                op.scope = maps[op.module].get(instruction(op.name),
                                               UNSCOPED)
            c = T.clip((op.start, op.end), window)
            if c:
                kept.append(ScopedOp(op.name, c[0], c[1], op.module,
                                     op.scope, op.leaf))
        chips.append(kept)
    return T.Reduced(window=window, chips=chips, spans=list(spans))


def _is_span(name: str) -> bool:
    return name.startswith((T.SPAN_PREFIX,) + PROGRAM_SPAN_PREFIXES)


def reduce(path: str, hlo: Optional[dict] = None,
           host_ops_as_chip: bool = False) -> T.Reduced:
    """The trace at ``path`` reduced as ``trace.reduce`` does, with the
    program's spans kept and each op's scope and leaf mark."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips, spans, cpu_ops = [], [], []
    ev = lambda e: (e.name, float(e.start_ns),
                    float(e.start_ns + e.duration_ns))
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = [ev(e) for e in lines[T.OPS_LINE].events] \
                if T.OPS_LINE in lines else []
            mods = [ev(e) for e in lines[T.MODULES_LINE].events] \
                if T.MODULES_LINE in lines else []
            chips.append((int(plane.name.rsplit(":", 1)[1]), ops, mods))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if _is_span(e.name):
                        spans.append(ev(e))
                    elif "hlo_module" in dict(e.stats):
                        cpu_ops.append(e)
    chips.sort()
    if not chips:
        if not (host_ops_as_chip and cpu_ops):
            raise ValueError(f"no /device:TPU:n plane in {path}")
        ops = [ev(e) for e in cpu_ops]
        mods = [(dict(e.stats)["hlo_module"], *ev(e)[1:]) for e in cpu_ops]
        chips = [(0, ops, mods)]
    return from_events([(o, m) for _, o, m in chips], spans, hlo=hlo)


def scope_ms(red: T.Reduced, program: str, calls: int) -> dict:
    """{scope: ms per call} of the leaf ops of the programs whose name
    matches ``program``, averaged over chips; ``""`` holds the ops with no
    model scope, so the values sum to the program's leaf time per call."""
    acc = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    rx = re.compile(program)
    for ops in red.chips:
        for o in ops:
            if o.leaf and rx.search(o.module):
                acc[o.scope] += o.end - o.start
    k = len(red.chips) * max(calls, 1)
    return {s: t / k * 1e-6 for s, t in acc.items()}


def has_scopes(red: T.Reduced) -> bool:
    """Whether any op carries a model scope (a program without
    ``named_scope`` gives none)."""
    return any(o.scope for ops in red.chips for o in ops)


def host_ms(red: T.Reduced, name: str) -> Optional[float]:
    """Per span ``name`` in the window: its wall time minus the chip's
    busy time inside it, in ms; None where there is no such span."""
    spans = T.spans_named(red, name)
    if not spans:
        return None
    wall = T.total(T.union(spans)) * 1e-9
    return 1e3 * (wall - T.device_s_within(red, spans)) / len(spans)


def idle_gaps(red: T.Reduced, n: int = 10) -> list:
    """[name, seconds]: the longest gaps with no op on chip 0, each named
    by the innermost span (the shortest) that covers at least half of
    it, else by the span that covers most of it."""
    return [[covering_span(red.spans, (s, e)), (e - s) * 1e-9]
            for s, e in _gaps(red)[:n]]


def _gaps(red: T.Reduced) -> list:
    b = T.busy(red.chips[0]) if red.chips else []
    edges = [red.window[0]] + [x for iv in b for x in iv] + [red.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def covering_span(spans, gap) -> str:
    half = (gap[1] - gap[0]) / 2
    best, best_key = "none", None
    for name, s, e in spans:
        if name == T.WINDOW:
            continue
        c = T.clip((s, e), gap)
        if c is None:
            continue
        cover = c[1] - c[0]
        key = (cover >= half, -(e - s) if cover >= half else cover)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best
