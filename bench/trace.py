"""The trace reducer: a profiler trace in, device time by name out.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into three
event lists: device operations (one line of each TPU plane), device
program executions (another line), and the host's annotation spans.
The functions after it are plain arithmetic over those lists, so that a
recorded trace checks them without a chip.

An operation is named by its HLO instruction (``%fusion.12 = ...`` is
``fusion.12``; a Pallas kernel keeps the kernel's name, such as
``paged_decode_attention.9``).  Operations nest (a ``while`` holds its
body's operations), so time by name is each operation's self time: its
duration less that of the operations inside it.  Busy time is the union
of the intervals in which an operation ran on a chip; the window is the
traced stretch, from the first event to the last; an idle gap is a
stretch of the window with no operation on the chip, labelled with the
harness's span around its middle and the innermost runtime span there
(the host and device clocks of a trace agree to about a millisecond).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Events:
    """(name, start_ns, end_ns, chip) per event, sorted by start."""

    ops: list
    modules: list
    host: list


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[0]
    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dst = ops if line.name == OPS_LINE else modules
                chip = int(m.group(1))
            elif plane.name.startswith("/host:"):
                dst, chip = host, -1
            else:
                continue
            for e in line.events:
                if dst is host and e.duration_ns <= 0:
                    continue
                name = e.name
                if dst is ops:
                    name = name.split(" = ", 1)[0].lstrip("%")
                dst.append((name, float(e.start_ns),
                            float(e.start_ns + e.duration_ns), chip))
    for lst in (ops, modules, host):
        lst.sort(key=lambda e: e[1])
    return Events(ops, modules, host)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def span(ev: Events) -> tuple[float, float]:
    """The traced stretch: first event start to last event end."""
    every = ev.ops + ev.modules + ev.host
    return min(e[1] for e in every), max(e[2] for e in every)


def busy_ns(ev: Events, chip: int) -> float:
    return sum(e - s for s, e in union(
        (o[1], o[2]) for o in ev.ops if o[3] == chip))


def time_by_name(events) -> dict[str, float]:
    """Total duration (ns) of the events of each name."""
    out: dict[str, float] = defaultdict(float)
    for name, s, e, _ in events:
        out[name] += e - s
    return dict(out)


def self_time_by_name(events) -> dict[str, float]:
    """Total self time (ns) of the events of each name: an event's
    duration less the durations of the events directly inside it (per
    chip; events sorted by start)."""
    out: dict[str, float] = defaultdict(float)
    stacks: dict[int, list] = defaultdict(list)
    for name, s, e, chip in events:
        stack = stacks[chip]
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            out[stack[-1][0]] -= e - s
        out[name] += e - s
        stack.append((name, e))
    return dict(out)


def count_by_name(events) -> dict[str, int]:
    out: dict[str, int] = defaultdict(int)
    for name, *_ in events:
        out[name] += 1
    return dict(out)


def gaps(ev: Events, chip: int, t0: float, t1: float):
    """Idle stretches (start, end) of ``chip`` inside [t0, t1]."""
    out, cur = [], t0
    for s, e in union((o[1], o[2]) for o in ev.ops if o[3] == chip):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


HARNESS_SPANS = ("bench.", "engine.run")


def label(ev: Events, t: float) -> str:
    """``harness span / innermost runtime span`` around time ``t``."""
    outer = inner = None
    for name, s, e, _ in ev.host:
        if s > t:
            break
        if e < t:
            continue
        if name.startswith(HARNESS_SPANS):
            if outer is None or s >= outer[1]:
                outer = (name, s)
        elif inner is None or s >= inner[1]:
            inner = (name, s)
    return " / ".join(x[0] for x in (outer, inner) if x) or "unattributed"


def module_kernels(ev: Events) -> dict[str, set]:
    """The operation names that run inside each program (module name)."""
    out: dict[str, set] = defaultdict(set)
    i = 0
    ops = ev.ops
    for name, s, e, chip in ev.modules:
        while i < len(ops) and ops[i][1] < s:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < e:
            if ops[j][3] == chip:
                out[name].add(ops[j][0])
            j += 1
    return dict(out)


def reduce(path: str, n_chips: int = 1, top: int = 10) -> dict:
    """Everything the per-layer readers take from a trace."""
    ev = load(path)
    if not ev.ops:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "op_counts": {},
                "modules": {}, "module_counts": {}, "module_kernels": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    t0, t1 = span(ev)
    chips = sorted({o[3] for o in ev.ops})[:n_chips]
    busy = sum(busy_ns(ev, c) for c in chips) / len(chips)
    ops = {k: v / len(chips) * 1e-9
           for k, v in self_time_by_name(ev.ops).items()}
    mods = {k: v / len(chips) * 1e-9
            for k, v in time_by_name(ev.modules).items()}
    idle = sorted(gaps(ev, chips[0], t0, t1), key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy * 1e-9, "window_s": (t1 - t0) * 1e-9,
        "ops": ops, "op_counts": count_by_name(ev.ops),
        "modules": mods, "module_counts": count_by_name(ev.modules),
        "module_kernels": {k: sorted(v)
                           for k, v in module_kernels(ev).items()},
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[label(ev, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in idle[:top]]},
    }


def programs(red: dict, *, named: str = "", running: str = ""):
    """(seconds, executions) of the programs whose module name contains
    ``named`` and whose operations include one containing ``running``."""
    secs, n = 0.0, 0
    for mod, t in red["modules"].items():
        if named not in mod:
            continue
        if running and not any(running in op
                               for op in red["module_kernels"].get(mod, ())):
            continue
        secs += t
        n += red["module_counts"][mod]
    return secs, n


def operations(red: dict, named: str):
    """(seconds, executions) of the operations whose name contains
    ``named``."""
    keys = [k for k in red["ops"] if named in k]
    return (sum(red["ops"][k] for k in keys),
            sum(red["op_counts"][k] for k in keys))
