#!/usr/bin/env python
"""Cut a recorded ``.xplane.pb`` down to a fixture: the device lines the
reduction reads and the benchmark's own host annotations, inside one time
range, as an XSpace text proto (``ProfileData.text_proto_to_serialized_xspace``
turns it back into the bytes the profiler writes).

    python benchmark/tools/trim_trace.py <in.xplane.pb> <out.txt> <from_ms> <to_ms>
"""
import sys


def esc(s):
    return s.replace("\\", "\\\\").replace('"', '\\"')


def main(src, dst, lo_ms, hi_ms):
    from jax.profiler import ProfileData

    keep_lines = {"XLA Ops", "Async XLA Ops", "XLA Modules", "Steps"}
    lo, hi = float(lo_ms) * 1e6, float(hi_ms) * 1e6
    out = []
    for pid, plane in enumerate(ProfileData.from_file(src).planes):
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        meta, lines = {}, []
        for lid, line in enumerate(plane.lines):
            if device and line.name not in keep_lines:
                continue
            if device:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.start_ns >= lo
                       and e.start_ns + e.duration_ns <= hi]
            else:   # the benchmark's annotations, clipped to the range
                evs = [(e.name, max(lo, e.start_ns),
                        min(hi, e.start_ns + e.duration_ns))
                       for e in line.events if e.name.startswith("bench.")
                       and e.start_ns < hi
                       and e.start_ns + e.duration_ns > lo]
            if not evs:
                continue
            t0 = min(s for _, s, _ in evs)
            rows = []
            for name, s, e in evs:
                mid = meta.setdefault(name[:96], len(meta) + 1)
                rows.append(
                    f"    events {{ metadata_id: {mid} offset_ps: "
                    f"{int(round((s - t0) * 1000))} duration_ps: "
                    f"{int(round((e - s) * 1000))} }}")
            lines.append(f'  lines {{ id: {lid} name: "{esc(line.name)}" '
                         f"timestamp_ns: {int(t0)}\n" + "\n".join(rows)
                         + "\n  }")
        if not lines:
            continue
        metas = "\n".join(
            f'  event_metadata {{ key: {i} value {{ id: {i} name: "{esc(n)}" }} }}'
            for n, i in meta.items())
        out.append(f'planes {{ id: {pid} name: "{esc(plane.name)}"\n'
                   + "\n".join(lines) + "\n" + metas + "\n}")
    with open(dst, "w") as f:
        f.write("\n".join(out) + "\n")


if __name__ == "__main__":
    main(*sys.argv[1:5])
