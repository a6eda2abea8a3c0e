"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer metrics
read. Nothing here knows a workload: names come in as arguments.

What a TPU trace holds (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip, with the lines ``Steps``, ``XLA Modules`` (one
event per run of a compiled program, named ``jit_<function>(<hash>)``),
``XLA Ops`` (one event per HLO instruction, named by its HLO text,
``%<instruction> = ...``; a ``while`` holds its body's events inside its own
span) and ``Async XLA Ops`` (copies and collectives in flight). The lines
cover the same time, so busy time is the UNION of the intervals of the
``XLA Ops`` line of one device, and over several devices the mean of their
unions. Host threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` is an event there on the same clock.
"""
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.trace_window"

_INSTR = re.compile(r"^%?([^\s=]+)")
_CONTAINER = re.compile(r"\s(while|conditional|call)\(")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


class TraceError(RuntimeError):
    """The trace cannot give what was asked of it."""


def instruction_name(event_name):
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    return _INSTR.match(event_name).group(1)


def family(event_name):
    """Instruction name without its trailing number: ``fusion.12`` ->
    ``fusion``."""
    return re.sub(r"[._]*\d+$", "", instruction_name(event_name)) or "op"


def union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def overlap(merged_a, merged_b):
    """Seconds-or-ns that two merged interval lists share."""
    i = j = 0
    acc = 0.0
    while i < len(merged_a) and j < len(merged_b):
        lo = max(merged_a[i][0], merged_b[j][0])
        hi = min(merged_a[i][1], merged_b[j][1])
        if hi > lo:
            acc += hi - lo
        if merged_a[i][1] < merged_b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def gaps(merged, lo, hi):
    """The idle intervals of [lo, hi] that ``merged`` leaves."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path):
    """The file as plain data: {"devices": {id: {line: [(name, start_ns,
    end_ns)]}}, "host": [(name, start_ns, end_ns)]} — only the lines the
    reduction uses, so tests can hand-make the same structure."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in (OP_LINE, ASYNC_LINE, MODULE_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def reduce_planes(planes, host_spans=(), n_devices=None):
    """The reduction. ``planes`` as :func:`read_planes` gives it.

    host_spans: [(name, start_s, end_s)] on the benchmark's own clock, with
      one span named ``WINDOW_ANNOTATION`` that the trace also holds as an
      annotation: that pair gives the clocks' offset and the window.
    Returns a dict; times in seconds, means over the devices.
    ``module_runs_s`` lists a module's runs that lie whole inside the window,
    ``module_busy_s`` is all its time inside the window, cut runs included. Modules are
    keyed by their name without the hash (``jit_local_step``), operations by
    their instruction family (``fusion``, ``flash_bwd_fused``).
    """
    devices = planes["devices"]
    if n_devices is not None:
        devices = {k: devices[k] for k in sorted(devices)[:n_devices]}
    if not devices or not any(d.get(OP_LINE) for d in devices.values()):
        raise TraceError("the trace holds no device operation: no plane "
                         f"'/device:TPU:<n>' with events on '{OP_LINE}'")
    marks = [h for h in planes["host"] if h[0] == WINDOW_ANNOTATION]
    mine = [s for s in host_spans if s[0] == WINDOW_ANNOTATION]
    if marks and mine:
        lo, hi = marks[-1][1], marks[-1][2]
        offset_ns = lo - mine[-1][1] * 1e9      # trace clock - host clock
    else:
        lo = min(e[1] for d in devices.values() for e in d.get(OP_LINE, ()))
        hi = max(e[2] for d in devices.values() for e in d.get(OP_LINE, ()))
        offset_ns = None
    if hi <= lo:
        raise TraceError("empty trace window")

    busy_ns, merged_by_dev = [], {}
    op_time = defaultdict(float)
    op_calls = defaultdict(int)
    module_runs = defaultdict(list)
    module_gaps = defaultdict(list)
    module_busy = defaultdict(float)
    coll_exposed = []
    for dev, lines in sorted(devices.items()):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in lines.get(OP_LINE, ())
               if min(e, hi) > max(s, lo)]
        merged = union([(s, e) for _, s, e in ops])
        merged_by_dev[dev] = merged
        busy_ns.append(total(merged))
        compute, coll = [], []
        for name, s, e in ops:
            if (_CONTAINER.search(name)
                    or family(name) in ("while", "conditional", "call")):
                continue
            if _COLLECTIVE.search(name):
                coll.append((s, e))
                op_time["collective"] += e - s
                continue
            compute.append((s, e))
            op_time[family(name)] += e - s
            op_calls[family(name)] += 1
        for name, s, e in lines.get(ASYNC_LINE, ()):
            if _COLLECTIVE.search(name) and min(e, hi) > max(s, lo):
                coll.append((max(s, lo), min(e, hi)))
        mc, mk = union(coll), union(compute)
        coll_exposed.append(total(mc) - overlap(mc, mk))
        by_module = defaultdict(list)
        for name, s, e in lines.get(MODULE_LINE, ()):
            key = name.split("(")[0]
            module_busy[key] += max(0.0, min(e, hi) - max(s, lo))
            if s >= lo and e <= hi:
                by_module[key].append((s, e))
        for key, runs in by_module.items():
            runs.sort()
            module_runs[key].extend(e - s for s, e in runs)
            module_gaps[key].extend(
                max(0.0, runs[i + 1][0] - runs[i][1])
                for i in range(len(runs) - 1))

    n = len(devices)
    window_ns = hi - lo
    busy = sum(busy_ns) / n
    out = {
        "window_s": window_ns / 1e9,
        "busy_s": busy / 1e9,
        "n_devices": n,
        "clock_offset_ns": offset_ns,
        "op_family_s": {k: v / n / 1e9 for k, v in op_time.items()},
        "op_family_calls": {k: v / n for k, v in op_calls.items()},
        "module_runs_s": {k: [x / 1e9 for x in v]
                          for k, v in module_runs.items()},
        "module_gaps_s": {k: [x / 1e9 for x in v]
                          for k, v in module_gaps.items()},
        "module_busy_s": {k: v / n / 1e9 for k, v in module_busy.items()},
        "collective_exposed_s": sum(coll_exposed) / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [], "span_uncovered_s": {},
    }
    if out["busy_s"] <= 0:
        raise TraceError("no device operation ran inside the traced window")
    if offset_ns is not None:
        first = merged_by_dev[sorted(merged_by_dev)[0]]
        spans = [(nm, s * 1e9 + offset_ns, e * 1e9 + offset_ns)
                 for nm, s, e in host_spans if nm != WINDOW_ANNOTATION]
        out["idle_gaps"] = _attribute(gaps(first, lo, hi), spans)
        unc = defaultdict(list)
        for nm, s, e in spans:
            if s >= lo and e <= hi:
                unc[nm].append(((e - s) - overlap([[s, e]], first)) / 1e9)
        out["span_uncovered_s"] = dict(unc)
    return out


def _attribute(idle, spans):
    """Idle seconds by the host span that covers each gap's middle; the
    shortest covering span wins (the innermost), ``(no span)`` otherwise."""
    acc = defaultdict(float)
    spans = sorted(spans, key=lambda s: s[2] - s[1])
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    acc["(gaps beyond the 2000 longest)"] = sum(
        e - s for s, e in idle[2000:]) / 1e9
    for s, e in idle[:2000]:
        mid = (s + e) / 2
        name = next((nm for nm, a, b in spans if a <= mid <= b), "(no span)")
        acc[name] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:10]]


def reduce_file(path, **kw):
    return reduce_planes(read_planes(path), **kw)
