"""The last line of a run: built and checked in one place.

``build`` returns the JSON text or raises :class:`LineError` saying what is
wrong; ``run.py`` prints through it only, last. A line that would break the
contract (a missing metric, a wrong unit, NaN, ``busy_s`` of 0 or above
``window_s``) is never printed: the run says why on an earlier line and exits
non-zero.
"""
import json
import math

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class LineError(ValueError):
    """The line would not be one the driver accepts."""


def _finite(x, what):
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise LineError(f"{what} is {x!r}, not a number")
    if not math.isfinite(x):
        raise LineError(f"{what} is {x!r}, not a finite number")
    return x


def declared(bench, cell, trace):
    """The metric entries of ``BENCHMARK.json`` that a run of ``cell`` has to
    report: with ``--trace 0`` its end-to-end metrics, with ``--trace 1`` its
    per-layer metrics. An entry without ``workloads`` belongs to every cell
    (end to end) or to every cell that reports the metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def check(line, metrics_declared, trace):
    """Raise LineError unless ``line`` (a dict) keeps the contract."""
    for k in KEYS:
        if k not in line:
            raise LineError(f"key {k!r} is missing")
    if not isinstance(line["correct"], bool):
        raise LineError(f"correct is {line['correct']!r}, not true or false")
    for k in ("attempted", "failed"):
        v = line[k]
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            raise LineError(f"{k} is {v!r}, not a count")
    if line["failed"] > line["attempted"]:
        raise LineError("failed exceeds attempted")
    metrics = line["metrics"]
    for m in metrics_declared:
        got = metrics.get(m["name"])
        if not isinstance(got, dict) or set(got) != {"value", "unit"}:
            raise LineError(f"metric {m['name']!r} is missing or is not "
                            f"{{value, unit}}: {got!r}")
        if got["unit"] != m["unit"]:
            raise LineError(f"metric {m['name']!r} has unit {got['unit']!r}, "
                            f"BENCHMARK.json says {m['unit']!r}")
        _finite(got["value"], f"metric {m['name']!r}")
    extra = set(metrics) - {m["name"] for m in metrics_declared}
    if extra:
        raise LineError(f"metrics not declared for this cell and mode: "
                        f"{sorted(extra)}")
    dev = line["device"]
    for k in DEVICE_KEYS:
        if k not in dev:
            raise LineError(f"device.{k} is missing")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"], str):
        raise LineError("device.platform and device.kind are strings")
    if _finite(dev["count"], "device.count") < 1:
        raise LineError("device.count is below 1")
    if _finite(dev["memory_peak_bytes"], "device.memory_peak_bytes") <= 0:
        raise LineError("device.memory_peak_bytes is not above 0")
    if trace:
        for k in ("busy_s", "window_s"):
            if k not in dev:
                raise LineError(f"device.{k} is missing in a traced run")
            _finite(dev[k], f"device.{k}")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise LineError(f"device.busy_s {dev['busy_s']} is not above 0 "
                            f"and at most window_s {dev['window_s']}")
    if "breakdown" in line:
        bd = line["breakdown"]
        for k in ("device_ops", "idle_gaps"):
            rows = bd.get(k)
            if not isinstance(rows, list) or len(rows) > 10:
                raise LineError(f"breakdown.{k} is not a list of at most 10")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str)):
                    raise LineError(f"breakdown.{k} row {row!r} is not "
                                    "[name, seconds]")
                _finite(row[1], f"breakdown.{k} {row[0]!r}")


def compared(checks):
    """{name: {"value", "limit"}} of a driver's checks, for the line's last
    key: every number ``correct`` compared beside its limit. What JSON cannot
    hold as it is (an infinite reading, a tuple) goes as its ``repr``."""
    def plain(x):
        if x is None or isinstance(x, (int, str)):     # bool is an int
            return x
        if isinstance(x, float):
            return x if math.isfinite(x) else repr(x)
        if isinstance(x, (list, tuple)):
            return [plain(y) for y in x]
        return repr(x)

    return {c["name"]: {"value": plain(c["value"]), "limit": plain(c["limit"])}
            for c in checks}


def build(*, correct, attempted, failed, values, metrics_declared, device,
          trace, breakdown=None, checks=None):
    """The line as text. ``values`` maps metric name -> number (or None for
    a reader that found nothing, which is left out and then fails the check
    where the cell declares it). ``checks`` (the driver's) go under
    ``compared``, the line's last key."""
    units = {m["name"]: m["unit"] for m in metrics_declared}
    line = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": units.get(k, "?")}
                    for k, v in values.items() if v is not None},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if checks is not None:
        line["compared"] = compared(checks)
    check(line, metrics_declared, trace)
    text = json.dumps(line, allow_nan=False)
    json.loads(text)
    return text
