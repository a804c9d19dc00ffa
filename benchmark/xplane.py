"""From a profiler trace (``.xplane.pb``) to numbers: device busy
seconds, the device operations and programs by time. Run as a program
(``python3 xplane.py <file-or-dir>``) it prints one JSON object; the
harness calls it that way after the server has gone, with JAX held to
the CPU, because reading the file needs ``jax.profiler.ProfileData``
and the harness itself never imports JAX.

A TPU's plane is named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds
one event for every operation that ran, ``XLA Modules`` one for every
execution of a compiled program; other lines (steps, TraceMe, the
SparseCore's) repeat or annotate them. Busy time is the union of the
``XLA Ops`` intervals (of all lines but ``Steps`` where a plane has no
such line), averaged over the device planes present.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ANNOTATION_LINES = ("Steps", "XLA TraceMe", "Framework Ops",
                     "Framework Name Scope", "Source code")


def find_xplane(path: str) -> str | None:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def union_ns(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def reduce_planes(planes) -> dict:
    """``planes``: iterable of (name, [(line name, [(event name,
    start_ns, duration_ns), ...]), ...])."""
    devices = [(n, lines) for n, lines in planes
               if n.startswith(DEVICE_PREFIXES)]
    busy, ops, modules = [], {}, {}
    for _name, lines in devices:
        names = [ln for ln, _ in lines]
        use = [OPS_LINE] if OPS_LINE in names else \
            [ln for ln in names if ln not in _ANNOTATION_LINES
             and ln != MODULES_LINE]
        spans = []
        for ln, events in lines:
            if ln in use:
                for ev, start, dur in events:
                    spans.append((start, start + dur))
                    ops[ev] = ops.get(ev, 0) + dur
            if ln == MODULES_LINE:
                for ev, start, dur in events:
                    n, t = modules.get(ev, (0, 0))
                    modules[ev] = (n + 1, t + dur)
        busy.append(union_ns(spans))
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    return {
        "devices": len(devices),
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "ops": [[k, v / 1e9] for k, v in top[:40]],
        "modules": [[k, n, t / 1e9] for k, (n, t) in sorted(
            modules.items(), key=lambda kv: -kv[1][1])[:20]],
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    out = reduce_planes(planes)
    out["planes"] = [[n, [[ln, len(evs)] for ln, evs in lines]]
                     for n, lines in planes]
    return out


def main(argv: list[str]) -> int:
    found = find_xplane(argv[1]) if len(argv) == 2 else None
    if found is None:
        print("usage: xplane.py <file.xplane.pb | directory>",
              file=sys.stderr)
        return 2
    print(json.dumps(reduce_file(found)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
