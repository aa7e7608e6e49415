"""Capture of a profiler trace, and its reduction to device metrics.

A traced run wraps its window in the host span ``bench:window`` and each
host phase of the harness in ``bench:<phase>``.  ``load`` reads the
``.xplane.pb`` the profiler wrote into plain tuples; ``reduce`` turns
them into:

  busy_s     seconds in which an operation ran on the device, within the
             window (union of op intervals), averaged over the devices;
  window_s   the window's length;
  scope_s    device seconds in which an op of each layer's
             ``jax.named_scope`` ran (union of its ops' intervals: ops
             can overlap on a TPU), per device;
  device_ops the ten ops (scope/instruction) that took most device time;
  idle_gaps  device idle time within the window, by the harness phase the
             host was in (the ``bench:`` span that overlaps each gap most).
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench:window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"

Op = collections.namedtuple("Op", "device name start end scope")
Span = collections.namedtuple("Span", "name start end")


def annotate(on: bool, phase: str):
    """The host span ``bench:<phase>`` when tracing, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(f"bench:{phase}")


@contextlib.contextmanager
def capture(on: bool):
    """Profile the body when ``on``; yields a list that receives the path
    of the ``.xplane.pb`` once the body has ended.  The directory is made
    under ``TMPDIR``; ``discard`` removes it."""
    out: list = []
    if not on:
        yield out
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(d)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out.extend(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True))
        out.append(d)


def discard(captured: list) -> None:
    if captured:
        shutil.rmtree(captured[-1], ignore_errors=True)


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scope_of(op_name: str, scopes) -> str:
    """The first layer scope that appears as a path component of an op's
    name metadata (``jit(f)/conv1_2/dot_general``), else ``""``."""
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return ""


def scope_map(hlo_texts, scopes) -> dict:
    """HLO instruction name -> layer scope, from the ``op_name`` metadata
    of compiled modules (``compiled.as_text()``).  A TPU trace names each
    op by its instruction and carries no metadata of its own."""
    out = {}
    scopes = frozenset(scopes)
    for text in hlo_texts:
        for line in text.splitlines():
            m, n = _INSTR.match(line), _OP_NAME.search(line)
            if m and n:
                scope = _scope_of(n.group(1), scopes)
                if scope:
                    out[m.group(1)] = scope
    return out


def load(path: str, scopes=(), hlo_texts=()) -> tuple:
    """(ops, spans) of a trace file: the device ops of every TPU plane,
    each with its instruction name and the layer scope ``scope_map`` gives
    it, and the host spans whose names start with ``bench:``."""
    from jax.profiler import ProfileData
    names = scope_map(hlo_texts, scopes)
    ops, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    m = _INSTR.match(ev.name)
                    name = m.group(1) if m else ev.name
                    ops.append(Op(plane.name, name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  names.get(name, "")))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    return ops, spans


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(busy, start, end):
    t, out = start, []
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if end > t:
        out.append((t, end))
    return out


def _phase_at(phases, starts, s, e) -> str:
    """The harness phase that overlaps [s, e) most.  ``phases`` are the
    ``bench:`` spans other than the window, which do not overlap one
    another, sorted by start; ``starts`` their starts."""
    best, most = "other", 0
    k = bisect.bisect_left(starts, e) - 1
    while k >= 0 and phases[k].end > s:
        ov = min(e, phases[k].end) - max(s, phases[k].start)
        if ov > most:
            best, most = phases[k].name[len("bench:"):], ov
        k -= 1
    return best


def reduce(ops, spans, top: int = 10) -> dict:
    """Device metrics over the ``bench:window`` span (see module doc).
    Returns ``None`` where the trace holds no window or no device op."""
    win = [s for s in spans if s.name == WINDOW]
    devices = sorted({o.device for o in ops})
    if not win or not devices:
        return None
    w0, w1 = win[0].start, win[0].end
    phases = sorted((s for s in spans if s.name != WINDOW),
                    key=lambda s: s.start)
    starts = [s.start for s in phases]
    busy_ns, scope_ns = 0.0, collections.Counter()
    kinds, gaps = collections.Counter(), collections.Counter()
    for dev in devices:
        clipped = [(max(o.start, w0), min(o.end, w1), o) for o in ops
                   if o.device == dev and o.end > w0 and o.start < w1]
        busy = _union((s, e) for s, e, _ in clipped)
        busy_ns += sum(e - s for s, e in busy)
        by_scope = collections.defaultdict(list)
        for s, e, o in clipped:
            if o.scope:
                by_scope[o.scope].append((s, e))
            kinds[f"{o.scope}/{o.name}" if o.scope else o.name] += e - s
        for scope, spans_ in by_scope.items():
            scope_ns[scope] += sum(e - s for s, e in _union(spans_))
        for s, e in _gaps(busy, w0, w1):
            gaps[_phase_at(phases, starts, s, e)] += e - s
    n = len(devices)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": n,
        "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in kinds.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gaps.most_common(top)],
    }


def idle_share(reduced) -> float:
    """Percent of the traced window in which no op ran on the device."""
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
