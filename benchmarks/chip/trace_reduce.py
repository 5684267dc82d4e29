"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's device
numbers.

What it computes, inside the traced window that the harness marks with
the host annotation `bench.traced`:

  busy_s      the union of the intervals in which a program ran on a
              device ("XLA Modules" line of each `/device:TPU:<i>` plane),
              averaged over the devices that ran anything;
  window_s    the length of the traced window;
  programs    device seconds and executions per jitted program, its name
              taken from the module ("jit_pbs_batch(12)" -> "pbs_batch"),
              of the executions that lie wholly inside the window;
  device_ops  device seconds per operation ("XLA Ops" line), by name, over
              the line's first `MAX_OPS` events: the engine room runs over
              a million operations a second, and the host reads each one;
  gaps        the idle intervals of the first device, longest first, each
              named after the host activity that covers it: the shortest
              host span that covers at least half of the gap, else the
              one that overlaps it most, else "no host span".

Host spans are the harness's own `jax.profiler.TraceAnnotation`s
(`bench.*`) and the program's `repro.obs` spans.  The latter are stamped
with `time.perf_counter()`; the harness opens the annotation
`bench.anchor` right after reading that clock, which puts both on the
profiler's clock.

Everything is read with `jax.profiler.ProfileData` and nothing else.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import itertools
import os
import re

WINDOW = "bench.traced"
MAX_OPS = 300_000
ANCHOR = "bench.anchor"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """A trace directory, an `.xplane.pb`, or a gzipped `.xplane.pb.gz`."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


_DEVICE = re.compile(r"^/device:TPU:\d+$")


def program_name(module: str) -> str:
    """'jit_pbs_batch(12)' -> 'pbs_batch'."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str, mods: list, starts: list, t: float) -> str:
    """'%fusion.12 = (...) fusion(...)' inside a run of jit_pbs_batch ->
    'pbs_batch/fusion.12'."""
    short = op.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][1] <= t <= mods[i][2]:
        return f"{program_name(mods[i][0])}/{short}"
    return short


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _events_of(lines: dict, name: str):
    return _events(lines[name]) if name in lines else iter(())


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def host_events(pd) -> list:
    """(name, start_ns, end_ns) of every event on the host plane."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend(_events(line))
    return out


def device_lines(pd) -> dict:
    """{device plane name: {line name: line}}; a line's events are read
    with `_events`, as far as they are needed."""
    return {plane.name: {line.name: line for line in plane.lines}
            for plane in pd.planes if _DEVICE.match(plane.name)}


def reduce(pd, spans=(), anchor_pc: float | None = None,
           top: int = 10) -> dict:
    """The window's device numbers; `spans` are (name, perf_counter start,
    duration seconds) host spans of the program, mapped onto the
    profiler's clock through the anchor annotation."""
    host = host_events(pd)
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = win[0]
    host_spans = [(n, s, e) for n, s, e in host
                  if n.startswith("bench.") and n not in (WINDOW, ANCHOR)]
    anchors = [s for n, s, _ in host if n == ANCHOR]
    if spans and anchors and anchor_pc is not None:
        off = anchors[0] - anchor_pc * 1e9
        host_spans += [(n, s * 1e9 + off, (s + d) * 1e9 + off)
                       for n, s, d in spans]

    devices = device_lines(pd)
    busy, programs, device_ops = [], {}, {}
    first_busy = None
    for dev in sorted(devices):
        lines = devices[dev]
        mods = [(n, *_clip(s, e, lo, hi)) for n, s, e in
                _events_of(lines, "XLA Modules") if e > lo and s < hi]
        if not mods:
            continue
        mods.sort(key=lambda m: m[1])
        merged = _union([(s, e) for _, s, e in mods])
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for n, s, e in mods:
            if s <= lo or e >= hi:
                continue        # cut by an edge of the window
            p = programs.setdefault(program_name(n), [0.0, 0])
            p[0] += (e - s) * 1e-9
            p[1] += 1
        starts = [s for _, s, _ in mods]
        for n, s, e in itertools.islice(_events_of(lines, "XLA Ops"),
                                        MAX_OPS):
            if e > lo and s < hi:
                s, e = _clip(s, e, lo, hi)
                key = op_name(n, mods, starts, s)
                device_ops[key] = device_ops.get(key, 0.0) + (e - s) * 1e-9

    gaps = []
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = [[_cover(host_spans, s, e), (e - s) * 1e-9]
                  for s, e in gaps[:top]]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "devices": len(busy),
        "programs": {k: {"seconds": v[0], "count": v[1]}
                     for k, v in sorted(programs.items())},
        "device_ops": sorted(([k, v] for k, v in device_ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": named_gaps,
    }


def _cover(spans, s, e) -> str:
    """The host activity that covers the idle interval [s, e)."""
    length = e - s
    best, best_overlap, cover, cover_len = None, 0.0, None, None
    for n, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        if ov > best_overlap:
            best, best_overlap = n, ov
        if ov >= 0.5 * length and (cover_len is None or b - a < cover_len):
            cover, cover_len = n, b - a
    return cover or best or "no host span"
