"""Device time per conv stage in a traced window, and the host event under
each device idle gap.

The program opens a ``jax.named_scope`` around each conv stage op
(``repro.conv.stages``: ``input_transform``, ``cgemm``, ``output_inverse``;
``repro.conv.backends``: ``direct``), so the compiled HLO's ``op_name``
metadata carries the stage as a path component below the caller's layer
scope.  ``reduce`` takes the ops and spans ``trace.load`` gave, the stage
of each instruction (``stage_map``) and the host events ``host_events``
read, and gives:

  stage_s           device seconds under each stage's scope within the
                    window (union of its ops' intervals, per device,
                    averaged over devices); 0 where no op carries it;
  layer_stage_s     the same per layer: ``{layer: {stage: seconds}}``;
  layer_unstaged_s  each layer's device seconds under no stage scope;
  idle_gap_host     for every device idle gap of at least 1 ms, the host
                    event (``<thread>/<event>``; not ``bench:``, not the
                    Python tracer's ``$…``) that overlaps it most: the five
                    largest totals ``[event, seconds, gaps]`` and the
                    longest gap ``[event, seconds]`` (``None`` without
                    such a gap).

The stage names are fixed here, not imported, so a program that opens no
stage scope reads 0 in every stage.
"""
from __future__ import annotations

import bisect
import collections
import re

from bench.lib.trace import _DEVICE_PLANE, WINDOW, Span, _gaps, _union, \
    scope_map

STAGES = ("input_transform", "cgemm", "output_inverse", "direct")
GAP_NS = 1_000_000


def stage_map(hlo_texts) -> dict:
    """HLO instruction name -> stage, from the compiled modules' ``op_name``
    metadata."""
    return scope_map(hlo_texts, STAGES)


def host_events(path: str) -> list:
    """Every host event of a trace file but the harness's (``bench:``) and
    the Python tracer's (``$…``), named ``<thread>/<event>``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            thread = re.sub(r"/\d+$", "", line.name)
            for ev in line.events:
                if not ev.name.startswith(("bench:", "$")):
                    out.append(Span(f"{thread}/{ev.name}", ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    return out


def _covered(intervals) -> float:
    return sum(e - s for s, e in _union(intervals))


def _host_at(events, starts, longest, s, e) -> str:
    """The host event that overlaps [s, e) most.  ``events`` may nest and
    overlap; sorted by start, ``starts`` their starts, ``longest`` the
    longest one's length."""
    best, most = "other", 0
    k = bisect.bisect_left(starts, e) - 1
    while k >= 0 and starts[k] > s - longest:
        ov = min(e, events[k].end) - max(s, events[k].start)
        if ov > most:
            best, most = events[k].name, ov
        k -= 1
    return best


def reduce(ops, spans, stage_of, host=()) -> dict:
    """Stage and idle-gap readings over the ``bench:window`` span (see
    module doc); ``stage_of`` maps an op's instruction name to its stage.
    ``None`` where the trace holds no window or no device op."""
    win = [s for s in spans if s.name == WINDOW]
    devices = sorted({o.device for o in ops})
    if not win or not devices:
        return None
    w0, w1 = win[0].start, win[0].end
    host = sorted(host, key=lambda h: h.start)
    starts = [h.start for h in host]
    longest = max((h.end - h.start for h in host), default=0)
    stage_ns = dict.fromkeys(STAGES, 0.0)
    pair_ns, unstaged_ns = collections.Counter(), collections.Counter()
    gap_ns, gap_n, longest_gap = (collections.Counter(),
                                  collections.Counter(), None)
    for dev in devices:
        clipped = [(max(o.start, w0), min(o.end, w1), o) for o in ops
                   if o.device == dev and o.end > w0 and o.start < w1]
        by_stage, by_pair = (collections.defaultdict(list),
                             collections.defaultdict(list))
        by_layer, staged = (collections.defaultdict(list),
                            collections.defaultdict(list))
        for s, e, o in clipped:
            stage = stage_of.get(o.name, "")
            if o.scope:
                by_layer[o.scope].append((s, e))
            if stage:
                by_stage[stage].append((s, e))
                if o.scope:
                    by_pair[o.scope, stage].append((s, e))
                    staged[o.scope].append((s, e))
        for stage, iv in by_stage.items():
            stage_ns[stage] += _covered(iv)
        for pair, iv in by_pair.items():
            pair_ns[pair] += _covered(iv)
        for layer, iv in by_layer.items():
            unstaged_ns[layer] += _covered(iv) - _covered(staged[layer])
        for s, e in _gaps(_union((s, e) for s, e, _ in clipped), w0, w1):
            if e - s >= GAP_NS:
                name = _host_at(host, starts, longest, s, e)
                gap_ns[name] += e - s
                gap_n[name] += 1
                if longest_gap is None or (e - s) / 1e9 > longest_gap[1]:
                    longest_gap = [name, (e - s) / 1e9]
    n = len(devices)
    layer_stage = collections.defaultdict(dict)
    for (layer, stage), v in sorted(pair_ns.items()):
        layer_stage[layer][stage] = v / n / 1e9
    return {
        "stage_s": {k: v / n / 1e9 for k, v in stage_ns.items()},
        "layer_stage_s": dict(layer_stage),
        "layer_unstaged_s": {k: v / n / 1e9 for k, v in unstaged_ns.items()},
        "idle_gap_host": {
            "top": [[k, v / n / 1e9, gap_n[k]]
                    for k, v in gap_ns.most_common(5)],
            "longest": longest_gap},
    }


def stage_ms(reduced, stage: str, steps: int):
    """Device milliseconds per step under ``stage``'s scope: 0 where the
    window holds no op of it, ``None`` without a trace or a step."""
    if not reduced or not steps:
        return None
    return 1000.0 * reduced["stage_s"][stage] / steps


def staged_share(reduced, scope_s) -> float:
    """Percent of the layers' device time (``trace.reduce``'s ``scope_s``)
    that falls under some stage scope; ``None`` without layer time."""
    layer_s = sum(scope_s.values())
    if not reduced or not layer_s:
        return None
    unstaged = sum(reduced["layer_unstaged_s"].values())
    return 100.0 * (1.0 - unstaged / layer_s)
