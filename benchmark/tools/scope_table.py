#!/usr/bin/env python3
"""One ``--trace 1`` run of a cell that ALSO reads, before the trace goes,
what the benchmark's reducer leaves out: the program scope of every device
operation, and the program's own spans as the profiler wrote them.

    python3 benchmark/tools/scope_table.py --workload <cell> --seed <n> --seconds <s>

It is ``run.py --trace 1`` with ``runtime.Run.reduce_trace`` extended, so the
run ends in the cell's ordinary result line. Before that it prints, and
writes to ``chiprun_out/scope_table/<cell>.json``:

- device seconds of the sub-window by program scope (``jax.named_scope``:
  ``optimizer_update``, ``grad_reduce``, ``attend_cache``, ``sample``) and
  instruction family. A TPU trace names an operation's scope in the ``tf_op``
  stat of its event METADATA (``jit(local_step)/.../optimizer_update/mul:``),
  which ``jax.profiler.ProfileData`` does not show, so the file is decoded
  here from its wire format;
- the clocks: each program span's ``TraceAnnotation`` in the trace against
  its ``time.perf_counter`` row shifted by the window's offset, and whether
  each run of ``--module`` on the device lies between the start of an
  ``--enqueue`` span and the end of the ``--wait`` span that follows it.

``analyse`` takes a path, so a trace brought back from the chip (a copy lands
beside the JSON) can be read again off the chip.
"""
import argparse
import json
import os
import re
import shutil
import struct
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import phases, runtime, trace  # noqa: E402

SCOPES = ("optimizer_update", "grad_reduce", "attend_cache", "sample")
_KIND = re.compile(r"kind=(k\w+)")
KEEP_TRACE_UNDER = 48 << 20


# -- the xplane file, decoded from its wire format ------------------------
def fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited fields, floats for doubles."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 1:
            val, i = struct.unpack_from("<d", buf, i)[0], i + 8
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind == 5:
            val, i = struct.unpack_from("<f", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, val


def _varint(buf, i):
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def read_space(path):
    """[{name, lines: [{name, events: [(metadata id, start ns, end ns)]}],
    events: {id: {name, stats: {stat name: value}}}}] of an XSpace file
    (field numbers of tsl/profiler/protobuf/xplane.proto)."""
    with open(path, "rb") as f:
        blob = f.read()
    planes = []
    for num, raw in fields(blob):
        if num != 1:
            continue
        plane = {"name": "", "lines": [], "events": {}}
        stat_names, metas = {}, []
        for f_, v in fields(raw):
            if f_ == 2:
                plane["name"] = v.decode()
            elif f_ == 3:
                plane["lines"].append(_line(v))
            elif f_ == 4:
                metas.append(dict(fields(v)).get(2, b""))
            elif f_ == 5:
                entry = dict(fields(dict(fields(v)).get(2, b"")))
                stat_names[_signed(entry.get(1, 0))] = entry.get(
                    2, b"").decode()
        for m in metas:
            meta = {"name": "", "stats": {}}
            for f_, v in fields(m):
                if f_ == 1:
                    meta["id"] = _signed(v)
                elif f_ == 2:
                    meta["name"] = v.decode(errors="replace")
                elif f_ == 5:
                    s = dict(fields(v))
                    val = s.get(5, s.get(4, s.get(3, s.get(2))))
                    if 7 in s:
                        val = stat_names.get(_signed(s[7]), "")
                    elif isinstance(val, bytes):
                        val = val.decode(errors="replace")
                    meta["stats"][stat_names.get(_signed(s.get(1, 0)),
                                                 "?")] = val
            plane["events"][meta.get("id", 0)] = meta
        planes.append(plane)
    return planes


def _line(raw):
    line = {"name": "", "events": []}
    t0_ns, events = 0, []
    for f_, v in fields(raw):
        if f_ == 2:
            line["name"] = v.decode()
        elif f_ == 3:
            t0_ns = _signed(v)
        elif f_ == 4:
            e = dict(fields(v))
            events.append((_signed(e.get(1, 0)), _signed(e.get(2, 0)),
                           _signed(e.get(3, 0))))
    line["events"] = [(mid, t0_ns + off / 1e3, t0_ns + (off + dur) / 1e3)
                      for mid, off, dur in events]
    return line


# -- the reading -----------------------------------------------------------
def scope_of(op_name, scopes=SCOPES):
    """``jit(step)/jvp(M)/optimizer_update/grad_reduce/mul:`` ->
    ``optimizer_update/grad_reduce``; ``(none)`` outside every scope."""
    found = [p for p in (op_name or "").rstrip(":").split("/") if p in scopes]
    return "/".join(found) or "(none)"


def analyse(path, program_rows=(), window_row=None, module="", enqueue="",
            wait="", operand="", n_devices=None, scopes=SCOPES):
    """The tables as a dict; ``program_rows`` as ``chainermn_tpu.tracing``
    gives them, ``window_row`` the run's ``(t0, t1)`` of the sub-window on
    ``time.perf_counter``. A fusion carries ONE name, its root's, so the
    scope of a fusion that the compiler built from several scopes is only
    the root's: ``by_family_kind_s`` splits a family by ``kind=`` (kOutput
    is rooted in a matmul) and ``reads_operand_s`` sums the operations
    that take an argument whose name holds ``operand``."""
    planes = read_space(path)
    host = [(p["events"][mid]["name"], s, e) for p in planes
            if p["name"].startswith("/host:CPU") for ln in p["lines"]
            for mid, s, e in ln["events"] if mid in p["events"]]
    marks = [h for h in host if h[0] == trace.WINDOW_ANNOTATION]
    devices = sorted((int(trace.DEVICE_PLANE.match(p["name"]).group(1)), p)
                     for p in planes if trace.DEVICE_PLANE.match(p["name"]))
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise trace.TraceError("the trace holds no '/device:TPU:<n>' plane")
    if marks:
        lo, hi = marks[-1][1], marks[-1][2]
    else:
        spans = [ev for _, p in devices for ln in p["lines"]
                 if ln["name"] == trace.OP_LINE for ev in ln["events"]]
        lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)

    seconds = defaultdict(float)        # (scope, family) -> device seconds
    kinds = defaultdict(lambda: defaultdict(float))
    reads = defaultdict(float)
    stats_seen, with_op_name, n_ops = set(), 0, 0
    module_runs = []
    for _, p in devices:
        for ln in p["lines"]:
            if ln["name"] == trace.MODULE_LINE and module:
                module_runs += [(s, e) for mid, s, e in ln["events"]
                                if p["events"][mid]["name"].startswith(module)
                                and s >= lo and e <= hi]
            if ln["name"] != trace.OP_LINE:
                continue
            for mid, s, e in ln["events"]:
                s, e = max(s, lo), min(e, hi)
                meta = p["events"].get(mid, {"name": "op", "stats": {}})
                name = meta["name"]
                if e <= s or trace._CONTAINER.search(name) or trace.family(
                        name) in ("while", "conditional", "call"):
                    continue
                n_ops += 1
                stats_seen.update(meta["stats"])
                op_name = meta["stats"].get("tf_op")
                with_op_name += op_name is not None
                fam = ("collective" if trace._COLLECTIVE.search(name)
                       else trace.family(name))
                seconds[(scope_of(op_name, scopes), fam)] += (e - s) / 1e9
                kind = _KIND.search(name)
                kinds[fam][kind.group(1) if kind else "-"] += (e - s) / 1e9
                if operand and operand in name.split("(", 1)[-1]:
                    reads[fam] += (e - s) / 1e9
    n = len(devices)
    by_family, by_scope = defaultdict(dict), defaultdict(float)
    for (scope, fam), v in seconds.items():
        by_family[fam][scope] = v / n
        by_scope[scope] += v / n
    out = {
        "window_s": (hi - lo) / 1e9, "n_devices": n, "device_ops": n_ops,
        "ops_with_tf_op": with_op_name,
        "metadata_stats_seen": sorted(stats_seen),
        "by_scope_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "by_family_s": {fam: dict(sorted(d.items(), key=lambda kv: -kv[1]))
                        for fam, d in sorted(
                            by_family.items(),
                            key=lambda kv: -sum(kv[1].values()))},
        "by_family_kind_s": {fam: {k: v / n for k, v in d.items()}
                             for fam, d in kinds.items()},
        "operand": operand,
        "reads_operand_s": {fam: v / n for fam, v in reads.items()},
    }
    if marks and window_row is not None:
        out["clocks"] = _clocks(host, program_rows, lo, hi,
                                lo - window_row[0] * 1e9)
        out["clocks"].update(_module_inside(host, module_runs, module,
                                            enqueue, wait))
    return out


def _clocks(host, program_rows, lo, hi, offset_ns):
    """k-th annotation of a name in the window against the k-th row of that
    name: the largest |start difference| and |duration difference|."""
    by_name = defaultdict(list)
    for name, s, e in sorted(host, key=lambda h: h[1]):
        if s >= lo and e <= hi:
            by_name[name].append((s, e))
    rows = defaultdict(list)
    for r in sorted(program_rows, key=lambda r: r.t0):
        rows[r.name].append((r.t0 * 1e9 + offset_ns, r.t1 * 1e9 + offset_ns))
    worst_start = worst_dur = 0.0
    matched, unmatched = 0, {}
    for name, mine in rows.items():
        theirs = by_name.get(name, [])
        if len(theirs) != len(mine):
            unmatched[name] = [len(mine), len(theirs)]
            continue
        for (s0, e0), (s1, e1) in zip(mine, theirs):
            worst_start = max(worst_start, abs(s1 - s0))
            worst_dur = max(worst_dur, abs((e1 - s1) - (e0 - s0)))
            matched += 1
    return {"clock_offset_ns": offset_ns, "spans_matched": matched,
            "rows_vs_annotations_unmatched": unmatched,
            "largest_start_difference_ms": worst_start / 1e6,
            "largest_duration_difference_ms": worst_dur / 1e6}


def _module_inside(host, runs, module, enqueue, wait):
    if not (module and enqueue and wait):
        return {}
    starts = sorted(s for n, s, e in host if n == enqueue)
    ends = sorted(e for n, s, e in host if n == wait)
    inside = 0
    for s, e in runs:
        began = [t for t in starts if t <= s]
        waited = [t for t in ends if began and t >= began[-1]]
        inside += bool(waited) and e <= waited[0]
    return {"module": module, "module_runs_in_window": len(runs),
            "module_runs_between_enqueue_and_wait_end": inside}


def say_tables(cell, out, top=12):
    print(f"scope_table {cell}: {out['device_ops']} device operations in "
          f"{out['window_s']:.3f} s on {out['n_devices']} device(s), "
          f"{out['ops_with_tf_op']} with a tf_op stat; metadata stats: "
          f"{', '.join(out['metadata_stats_seen'])}", flush=True)
    print("scope_table by scope: " + ", ".join(
        f"{k} {v:.4f}" for k, v in out["by_scope_s"].items()), flush=True)
    for fam, d in list(out["by_family_s"].items())[:top]:
        extra = "; by kind " + ", ".join(
            f"{k} {v:.4f}" for k, v in out["by_family_kind_s"][fam].items())
        if fam in out["reads_operand_s"]:
            extra += (f"; reading {out['operand']!r} "
                      f"{out['reads_operand_s'][fam]:.4f}")
        print(f"scope_table family {fam} {sum(d.values()):.4f} s: "
              + ", ".join(f"{k} {v:.4f}" for k, v in d.items()) + extra,
              flush=True)
    if "clocks" in out:
        print("scope_table clocks " + json.dumps(out["clocks"]), flush=True)


class ScopeRun(runtime.Run):
    """``runtime.Run`` whose reduction reads the trace once more first."""

    tool = {}       # --module, --enqueue, --wait, --operand
    dest = os.path.join(ROOT, "chiprun_out", "scope_table")

    def reduce_trace(self, **kw):
        path = trace.newest_xplane(self._trace_dir)
        window = self.spans.named(trace.WINDOW_ANNOTATION)[-1]
        rows = phases.program_rows(self.spans) or []    # None: no spans
        out = analyse(path, rows, window, n_devices=len(self.devices),
                      **self.tool)
        cell = self.cell["name"]
        os.makedirs(self.dest, exist_ok=True)
        with open(os.path.join(self.dest, cell + ".json"), "w") as f:
            json.dump(out, f, indent=1)
        if os.path.getsize(path) < KEEP_TRACE_UNDER:
            shutil.copy(path, os.path.join(self.dest, cell + ".xplane.pb"))
        say_tables(cell, out)
        return super().reduce_trace(**kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--module", default="jit__decode_k")
    ap.add_argument("--enqueue", default="engine.decode.enqueue")
    ap.add_argument("--wait", default="engine.decode.wait")
    ap.add_argument("--operand", default="state_1__",
                    help="mark operations that read an argument whose name "
                         "holds this text (the step's optimizer state)")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run

    ScopeRun.tool = dict(module=args.module, enqueue=args.enqueue,
                         wait=args.wait, operand=args.operand)
    runtime.Run = ScopeRun
    return bench_run.main(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
