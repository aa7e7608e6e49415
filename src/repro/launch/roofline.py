"""Roofline-term extraction from compiled XLA artifacts.

Hardware model: per-chip peaks from ``PEAKS``, keyed by
``jax.Device.device_kind``.  A device kind that is not in the table is an
error, never a default.

Terms (seconds):
    compute    = HLO_FLOPs  / (chips * flops)
    memory     = HLO_bytes  / (chips * hbm_bw)
    collective = coll_bytes / (chips * link_bw)

``cost_analysis()`` of a GSPMD-partitioned executable describes the
*per-device* program, so per-device values are multiplied by the chip count
to match the formula's global convention (the two normalisations cancel —
term == per_device_value / per_chip_rate).

Collective bytes are NOT in cost_analysis: we parse the compiled HLO text
and sum the result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (async ``-start`` ops
counted once; ``-done`` skipped).
"""
from __future__ import annotations

import collections
import re

Peaks = collections.namedtuple("Peaks", ["flops", "hbm_bw", "link_bw"])

# Per-chip peaks by device kind.  TPU v5e ("TPU v5 lite"): Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The ``PEAKS`` row for ``device_kind`` (a ``Device.device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# e.g.  %all-reduce.5 = f32[9,32,256]{2,1,0} all-reduce(%x), ...
#       %ag = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) all-gather-start(...)
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->.*{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?\)[^\n]*?condition=%?([\w.\-]+)[^\n]*?body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:call|fusion|conditional)\(.*?\)[^\n]*?"
                      r"(?:to_apply|called_computations)=\{?%?([\w.\-]+)")
_TRIP_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict:
    """computation name -> its text block."""
    comps, cur, buf = {}, None, []
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and line.rstrip().endswith("{"):
            if cur is not None:
                comps[cur] = "\n".join(buf)
            cur, buf = m.group(1), []
        elif cur is not None:
            buf.append(line)
            if line.strip() == "}":
                comps[cur] = "\n".join(buf)
                cur = None
                buf = []
    if cur is not None:
        comps[cur] = "\n".join(buf)
    return comps


def _trip_count(cond_text: str) -> int:
    """Heuristic: the largest integer constant in the loop condition."""
    consts = [int(c) for c in _TRIP_RE.findall(cond_text)]
    return max(consts) if consts else 1


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective bytes by op kind (result-shape proxy).

    XLA cost analysis counts while-loop bodies ONCE; the same is true of a
    naive text scan. We therefore walk the call graph: collective bytes
    found inside a while body are multiplied by the loop trip count
    (extracted from the loop condition), recursively — a collective inside
    the flash-attention scan inside the layer scan is counted
    trip_inner x trip_outer times.
    """
    comps = _split_computations(hlo_text)

    def block_stats(text):
        out = {k: 0 for k in _COLL_KINDS}
        counts = {k: 0 for k in _COLL_KINDS}
        for m in _OP_RE.finditer(text):
            shape_text, kind = m.group(1), m.group(2)
            out[kind] += _shape_bytes(shape_text)
            counts[kind] += 1
        return out, counts

    # multipliers via DFS from every root (entry = any comp not referenced)
    referenced = set()
    edges = {}              # comp -> [(child, mult)]
    for name, text in comps.items():
        ch = []
        for m in _WHILE_RE.finditer(text):
            cond, body = m.group(1), m.group(2)
            trip = _trip_count(comps.get(cond, ""))
            ch.append((body, trip))
            referenced.update((cond, body))
        for m in _CALL_RE.finditer(text):
            ch.append((m.group(1), 1))
            referenced.add(m.group(1))
        edges[name] = ch

    entry = [n for n in comps if n not in referenced]
    mult = {n: 0 for n in comps}

    def visit(name, m, depth=0):
        if name not in comps or depth > 12:
            return
        mult[name] = mult.get(name, 0) + m
        for child, t in edges.get(name, ()):
            visit(child, m * t, depth + 1)

    for e in (entry or list(comps)[:1]):
        visit(e, 1)

    out = {k: 0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for name, text in comps.items():
        b, c = block_stats(text)
        m = max(mult.get(name, 0), 0)
        for k in _COLL_KINDS:
            out[k] += b[k] * m
            counts[k] += c[k] * m
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float, *, device_kind: str) -> dict:
    pk = peaks(device_kind)
    comp = flops_per_dev / pk.flops
    mem = bytes_per_dev / pk.hbm_bw
    coll = coll_bytes_per_dev / pk.link_bw
    dom = max(("compute", comp), ("memory", mem), ("collective", coll),
              key=lambda kv: kv[1])
    total = max(comp, mem, coll)
    return {
        "compute_s": comp, "memory_s": mem, "collective_s": coll,
        "dominant": dom[0],
        # fraction of roofline: how close the *dominant* term is to being
        # the only cost (1.0 == perfectly balanced on the bottleneck)
        "bound_s": total,
    }


def model_flops(n_params_active: int, tokens: int, *, train: bool) -> float:
    """MODEL_FLOPS = 6*N*D for training (fwd+bwd), 2*N*D for inference."""
    return (6.0 if train else 2.0) * n_params_active * tokens
