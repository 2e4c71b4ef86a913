"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain events: each device's
operations (name, start, duration, the name-scope path the program gave
them) and the benchmark's own host spans (names starting ``bench.``). The
trace names each operation by its HLO instruction and carries no scope;
``scope_paths`` reads the scopes from the ``op_name`` metadata of the
compiled program's HLO text, by instruction name.
``reduce`` turns those into the device's busy time, the time under each of
the scopes it is given (those the cell's per-layer metrics read,
``manifest.Cell.scopes``), the operations that took longest, and the
longest idle gaps, each tagged with the host span it fell in. The plain
form is what the test fixture holds, so the reduction is checked on a
trace recorded on the chip.
"""
from __future__ import annotations

import gzip
import json
import re
from typing import Collection, Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_TOKEN = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")
_DEF = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*metadata=\{op_name="([^"]*)"')

Span = Tuple[str, float, float]             # name, start ns, dur ns


def scope_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` path, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def op_name(event_name: str) -> str:
    """``fusion.12`` of a trace event named ``%fusion.12 = f32[...] ...``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str, paths: Dict[str, str]) -> Dict[str, object]:
    """Plain events of an ``.xplane.pb``: ``{"devices": [{"name", "ops"}],
    "spans": [...]}``. Device planes are those named ``/device:*`` that
    have an ``XLA Ops`` line; ``paths`` maps instruction names to their
    scope paths (``scope_paths``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = []
                for ev in line.events:
                    name = op_name(ev.name)
                    ops.append((name, float(ev.start_ns),
                                float(ev.duration_ns), paths.get(name, "")))
                devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)))
    return {"devices": devices, "spans": spans}


def save_plain(events: Dict[str, object], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_plain(path: str) -> Dict[str, object]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def scope_of(path: str, scopes: Collection[str]) -> Optional[str]:
    """The innermost of ``scopes`` (the program's ``jax.named_scope``
    names) in an op's path; a transposed op keeps its scope in its
    path."""
    found = None
    for tok in _TOKEN.findall(path):
        if tok in scopes:
            found = tok
    return found


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def exclusive(ops: Sequence[Tuple[float, float]]) -> List[float]:
    """Each interval's duration less the parts of it that intervals
    nested inside it cover (an enclosing op gets only its own time)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e in ops]
    stack: List[int] = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(e, ops[parent][1]) - s
        stack.append(i)
    return [max(x, 0.0) for x in own]


def window_of(spans: Sequence[Span], name: str) -> Tuple[float, float]:
    """``(start, end)`` of the first host span called ``name``."""
    for n, s, d in spans:
        if n == name:
            return s, s + d
    raise ValueError(f"no host span {name!r} in the trace")


def _tag(spans: Sequence[Span], t: float) -> str:
    """The innermost benchmark span around time ``t``, without its
    prefix; ``"none"`` where no span covers it."""
    best, width = "none", float("inf")
    for n, s, d in spans:
        if s <= t < s + d and d < width:
            best, width = n[len(SPAN_PREFIX):], d
    return best


def reduce(events: Dict[str, object], window: Tuple[float, float],
           scopes: Collection[str], top: int = 10) -> Dict[str, object]:
    """Busy and idle time, time per scope of ``scopes`` and the top
    operations and idle gaps, over the window ``(start_ns, end_ns)``. Busy
    time is averaged over the devices; scope times, ops and gaps are
    summed over them."""
    t0, t1 = window
    spans = events["spans"]
    busy, scope_ns, by_name, gaps = [], {}, {}, []
    for dev in events["devices"]:
        ops = [(n, max(s, t0), min(s + d, t1), p)
               for n, s, d, p in dev["ops"] if s < t1 and s + d > t0]
        merged = union([(s, e) for _, s, e, _ in ops])
        busy.append(sum(e - s for s, e in merged))
        own = exclusive([(s, e) for _, s, e, _ in ops])
        for (name, _, _, path), ns in zip(ops, own):
            sc = scope_of(path, scopes)
            if sc is not None:
                scope_ns[sc] = scope_ns.get(sc, 0.0) + ns
            tail = path.rsplit("/", 1)[-1]
            key = f"{name} [{sc or '-'}] {tail}".rstrip()
            by_name[key] = by_name.get(key, 0.0) + ns
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_tag(spans, s), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9 if busy else 0.0,
        "devices": len(busy),
        "scope_s": {k: v * 1e-9 for k, v in scope_ns.items()},
        "device_ops": [[k, v * 1e-9] for k, v in ops_top],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }
