"""From a profiler trace to numbers: the device's busy time, its
operations, the host annotations, the idle gaps and what the host was
doing in them; the table of peaks; and the least time the chip could take
for the columns a cell coded.

Two steps, so that the arithmetic can be checked on a recorded trace
without JAX: `extract` reads an `.xplane.pb` with `jax.profiler.ProfileData`
into plain lists, `summarize` reduces those.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

# Published peaks of one chip, keyed by `device_kind` as JAX reports it.
# A device that is not here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s",
    },
}

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to DEVICE_PEAKS with its "
                       "source")
    return DEVICE_PEAKS[device_kind]


def gf_apply_work(k: int, rows_out: int, columns: int) -> dict:
    """What applying a rows_out x k GF(2^8) matrix to `columns` byte
    columns needs, whatever implements it: k bytes in and rows_out bytes
    out per column through HBM; as a bit-matrix product, the 8*rows_out x
    8*k binary matrix times the column's 8*k bits: 2*64*k*rows_out
    integer operations per column."""
    return {"hbm_bytes": (k + rows_out) * columns,
            "int8_ops": 2 * 64 * k * rows_out * columns}


def least_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """The roofline: the larger of bytes over the HBM peak and
    operations over the int8 peak, and which of the two it is."""
    p = peaks(device_kind)
    by_bytes = work["hbm_bytes"] / p["hbm_bytes_per_s"]
    by_ops = work["int8_ops"] / p["int8_ops_per_s"]
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "int8")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str) -> dict:
    """Planes, lines and events as plain lists: an event is
    [name, start in ns, duration in ns] on the trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        stats = {}
        for key, value in plane.stats:
            if key in ("profile_start_time", "profile_stop_time"):
                stats[key] = float(value)
        planes.append({"name": plane.name, "lines": lines, "stats": stats})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    """A trace kept by `extract` as (gzipped) JSON."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def union(intervals: list[tuple[float, float]]
          ) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def overlap(lo: float, hi: float,
            merged: list[tuple[float, float]]) -> float:
    """How much of [lo, hi) the sorted, disjoint `merged` cover."""
    total = 0.0
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    while i < len(merged) and merged[i][0] < hi:
        total += max(0.0, min(hi, merged[i][1]) - max(lo, merged[i][0]))
        i += 1
    return total


def summarize(raw: dict | str) -> dict:
    """All that the per-layer readers and the breakdown need, in seconds.

    - window_s: the profiler session's own start-to-stop time.
    - busy_s: per device, the union of the intervals in which an
      operation ran (the `XLA Ops` line), averaged over the devices that
      ran any.
    - device_ops: name -> [count, seconds], summed over devices.
    - host: name -> [count, seconds] of every host event (the program's
      `TraceAnnotation`s among them), nested repeats of a name on one
      thread counted once.
    - idle_gaps: label -> seconds of device idle time inside the window,
      each gap labelled by the host span that covers most of it (see
      `names_a_span`), or "no annotation"."""
    if isinstance(raw, str):
        raw = extract(raw)
    start = stop = None
    for plane in raw["planes"]:
        start = plane["stats"].get("profile_start_time", start)
        stop = plane["stats"].get("profile_stop_time", stop)
    device_ops: dict[str, list[float]] = {}
    busy_by_device = []
    lo_seen, hi_seen = float("inf"), float("-inf")
    for plane in raw["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        spans = []
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            for name, s, d in line["events"]:
                spans.append((s, s + d))
                rec = device_ops.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += d / 1e9
        if spans:
            busy_by_device.append(union(spans))
            lo_seen = min(lo_seen, busy_by_device[-1][0][0])
            hi_seen = max(hi_seen, busy_by_device[-1][-1][1])
    host: dict[str, list[float]] = {}
    program_spans: dict[str, list[tuple[float, float]]] = {}
    for plane in raw["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            by_name: dict[str, list[tuple[float, float]]] = {}
            for name, s, d in line["events"]:
                by_name.setdefault(name, []).append((s, s + d))
            for name, spans in by_name.items():
                rec = host.setdefault(name, [0, 0.0])
                rec[0] += len(spans)
                rec[1] += sum(b - a for a, b in union(spans)) / 1e9
                if names_a_span(name):
                    program_spans.setdefault(name, []).extend(spans)
    if start is not None and stop is not None and stop > start:
        window_s = (stop - start) / 1e9
    elif hi_seen > lo_seen:
        window_s = (hi_seen - lo_seen) / 1e9
    else:
        window_s = 0.0
    busy_s = (sum(sum(b - a for a, b in u) for u in busy_by_device)
              / len(busy_by_device) / 1e9) if busy_by_device else 0.0
    merged_notes = {n: union(s) for n, s in program_spans.items()}
    gaps: dict[str, float] = {}
    # events are timed from the session's start, so the window's two
    # ends (before the first operation, after the last) are gaps too
    edge = window_s * 1e9 if start is not None and stop is not None else None
    for u in busy_by_device:
        ends = [(0.0, 0.0)] + u + [(edge, edge)] if edge else u
        for (_, a), (b, _) in zip(ends, ends[1:]):
            if b <= a:
                continue
            best, best_cover = "no annotation", 0.0
            for name, spans in merged_notes.items():
                cover = overlap(a, b, spans)
                if cover > best_cover and cover >= 0.5 * (b - a):
                    best, best_cover = name, cover
            gaps[best] = gaps.get(best, 0.0) + (b - a) / 1e9
    n_dev = max(1, len(busy_by_device))
    return {"window_s": window_s, "busy_s": busy_s,
            "devices_traced": len(busy_by_device),
            "device_ops": device_ops, "host": host,
            "idle_gaps": {k: v / n_dev for k, v in gaps.items()}}


def names_a_span(name: str) -> bool:
    """Host events that may label an idle gap: the program's annotations
    and the runtime's named phases, not its call signatures."""
    return not any(c in name for c in ":($ /<")


def short_op(name: str) -> str:
    """`%gf_apply.1 = u8[4,8388608]{...} custom-call(...)` ->
    `gf_apply.1 u8[4,8388608]`: the operation and its result's shape."""
    m = re.match(r"%?([\w.\-]+) = ([^{ ]+)", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def breakdown(summary: dict) -> dict:
    ops: dict[str, float] = {}
    for name, rec in summary["device_ops"].items():
        ops[short_op(name)] = ops.get(short_op(name), 0.0) + rec[1]
    ops = sorted(([n, s] for n, s in ops.items()),
                 key=lambda x: -x[1])[:10]
    gaps = sorted(([n, s] for n, s in summary["idle_gaps"].items()),
                  key=lambda x: -x[1])[:10]
    return {"device_ops": ops, "idle_gaps": gaps}


# --- what several per-layer readers share (a reader is a file of its own
# under layer_metrics/; `run` is what run.py hands it) ---

def idle_pct(run: dict) -> float | None:
    """Share of the traced window in which no operation ran on the
    device. Nothing traced on a device: nothing to read."""
    t = run["trace"]
    if not t["devices_traced"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_pct(run: dict) -> float | None:
    """Least time the chip could take for the columns the cell coded in
    the window (from the geometry and the bytes the harness saw coded,
    never from the kernel's shapes), over the device's busy time."""
    t, facts = run["trace"], run["facts"]
    if not t["busy_s"] or not facts.get("columns_coded"):
        return None
    k = int(run["config"]["geometry"].split("+")[0])
    work = gf_apply_work(k, facts["rows_out"], facts["columns_coded"])
    least, _ = least_seconds(work, run["device_kind"])
    return 100.0 * least / run["chips"] / t["busy_s"]
