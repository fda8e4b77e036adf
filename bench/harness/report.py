"""The result line: the comparison judged against its limits, the metrics
the cell reports, and printing."""
from __future__ import annotations

import json
import sys

from bench.harness import cell as cellmod


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Every limit needs a reading at or under it; a missing or non-finite
    reading fails."""
    for name, value in readings.items():
        if name not in limits:
            print(f"reading {name}: {value!r} (not compared)",
                  file=sys.stderr)
    lines, ok = {}, True
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        lines[name] = {"value": value, "limit": limit}
    return ok and bool(limits), lines


def end_to_end(cell, values: dict, setup_s: float) -> dict:
    out = {}
    for m in cell.end_to_end:
        v = setup_s if m["name"] == "setup_s" else values.get(m["name"])
        if v is None:
            raise KeyError(f"the {cell.mix['kind']} runner does not measure "
                           f"{m['name']}")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def per_layer(cell, drv, summary, device) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    ctx = {"cell": cell, "run": drv, "counters": drv.counters(),
           "trace": summary,
           "peaks": cellmod.peaks(cell.root, device.device_kind),
           "kernel": lambda name: cellmod.kernel_count(cell.root, name)}
    out = {}
    for m in cell.per_layer:
        v = cellmod.metric_reader(cell.root, m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
