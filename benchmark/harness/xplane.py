"""Reduce a profiler trace (``.xplane.pb``) to what the layer metrics read.

Run as a script in a process of its own with ``JAX_PLATFORMS=cpu`` — it is
the only benchmark code that imports jax (for ``ProfileData``), and the
benchmark's parent process must never touch JAX:

    python benchmark/harness/xplane.py <file.xplane.pb>   # JSON on stdout

``reduce`` itself is a pure function over plain tuples, tested on
hand-made samples and on a small recorded trace.
"""

from __future__ import annotations

import json
import re
import sys

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def load(path: str) -> list:
    """[(plane, line, [(name, start_ns, dur_ns), ...]), ...]"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            out.append((plane.name, line.name, evs))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()


def union_s(intervals) -> float:
    """Seconds covered by the union of (start_ns, dur_ns) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def family(name: str) -> str:
    """The op's own name without its instance number: the trace names an
    op by its whole HLO line ("%fusion.324 = bf16[...] fusion(...)"), and
    fusion.324 and fusion.77 are one family, as are a kernel's calls."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head or name


def self_times(events) -> list:
    """[(name, self_ns)] for one line's events: an op that contains others
    (a while loop and its body) keeps only the time none of them covers."""
    out, stack = [], []     # stack of [name, end_ns, self_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    out.extend((n, t) for n, _, t in stack)
    return out


def module_name(name: str) -> str:
    """jit__decode_multi_packed_step(1234567) -> jit__decode_multi_packed_step"""
    return re.sub(r"\(.*$", "", name).strip()


def reduce(lines: list, top: int = 10) -> dict:
    """Per device plane: the traced span, the busy time (union of the op
    line's intervals; the module line's where a plane has no op line), the
    device time per XLA module, the op families that took most (self)
    time and the idle time between modules, by the modules on either
    side."""
    planes: dict = {}
    for plane, line, evs in lines:
        if is_device_plane(plane):
            planes.setdefault(plane, {})[line] = evs
    out = {"planes": sorted({p for p, _, _ in lines}), "devices": {}}
    for plane, by_line in sorted(planes.items()):
        mods = by_line.get(MODULES_LINE, [])
        ops = by_line.get(OPS_LINE, [])
        busy_src = ops or mods
        if not busy_src:
            continue
        start = min(s for _, s, _ in busy_src)
        end = max(s + d for _, s, d in busy_src)
        modules: dict = {}
        for name, _, d in mods:
            m = modules.setdefault(module_name(name),
                                   {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += d / 1e9
        fam: dict = {}
        for name, d in self_times(ops):
            fam[family(name)] = fam.get(family(name), 0.0) + d / 1e9
        gaps = []
        seq = sorted((s, s + d, module_name(n)) for n, s, d in mods)
        for (_, e0, n0), (s1, _, n1) in zip(seq, seq[1:]):
            if s1 > e0:
                gaps.append((f"{n0}->{n1}", (s1 - e0) / 1e9))
        by_kind: dict = {}
        for name, g in gaps:
            by_kind[name] = by_kind.get(name, 0.0) + g
        out["devices"][plane] = {
            "lines": {ln: len(e) for ln, e in by_line.items()},
            "span_s": (end - start) / 1e9,
            "busy_s": union_s((s, d) for _, s, d in busy_src),
            "modules": modules,
            "device_ops": sorted(([n, t] for n, t in fam.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": sorted(([n, t] for n, t in by_kind.items()),
                                key=lambda x: -x[1])[:top],
        }
    return out


if __name__ == "__main__":
    json.dump(reduce(load(sys.argv[1])), sys.stdout)
