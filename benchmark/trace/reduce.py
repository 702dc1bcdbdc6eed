"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Two steps, so that the arithmetic can be checked on a small recorded trace
without a profiler: ``load_xplane`` turns the file into plain lists of events
(``events`` below), and ``reduce`` turns those into

- ``window_s``: the first device op's start to the last one's end (what the
  profiler's own start and stop cost the host lies outside it);
- ``busy_s``: per device the union of the intervals in which an XLA op ran,
  averaged over the devices that ran any; ``busy_by_device``;
- ``ops``: seconds and calls by op name (mean over devices; ops that only hold
  other ops, as a ``while``, left out), and ``modules`` likewise for whole
  compiled programs;
- ``idle_gaps``: the gaps of the first device's busy union, each given to the
  host span that was innermost at its middle, summed by span name.

``events`` = ``{"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
"modules": [[name, start_ns, dur_ns], ...]}}, "host": [[thread, name,
start_ns, dur_ns], ...]}``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Tuple

import numpy as np

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: Gaps shorter than this are the device's own turn-around between ops.
MIN_GAP_NS = 20_000
MAX_GAPS = 400
#: Ops that only hold other ops (their time is their children's).
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^%?([\w.\-]+) = (\w+)\[([\d,]*)\]")


def short_name(text: str) -> str:
    """``%convert.18 = bf16[24,8192,2048]{...} convert(...)`` ->
    ``convert.18_bf16_24_8192_2048``; anything else: its first word."""
    m = _HLO.match(text)
    if m:
        dims = m.group(3).replace(",", "_")
        return f"{m.group(1)}_{m.group(2)}" + (f"_{dims}" if dims else "")
    return text.split(" ", 1)[0].lstrip("%")[:120]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name in OPS_LINES:
                    dev["ops"] = [[short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                                  for e in line.events]
                elif line.name in MODULE_LINES:
                    dev["modules"] = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
            if dev["ops"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append([line.name, e.name, int(e.start_ns), int(e.duration_ns)])
    return {"devices": devices, "host": host}


def union_intervals(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted intervals of the union."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    merged_end = np.append(run_end[idx[1:] - 1], run_end[-1])
    return s[idx], merged_end


def _by_name(rows: List[list], n_devices: int) -> List[List[Any]]:
    total: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    for name, _, dur in rows:
        total[name] += dur
        calls[name] += 1
    out = [[n, total[n] / 1e9 / n_devices, calls[n] / n_devices] for n in total]
    out.sort(key=lambda r: -r[1])
    return out


def reduce(events: Dict[str, Any]) -> Dict[str, Any]:
    devices = events["devices"]
    if not devices:
        raise ValueError("no operation ran on a device in the traced window")
    host = events.get("host") or []
    t_min, t_max = None, None
    busy_by_device: Dict[str, float] = {}
    unions = {}
    all_ops: List[list] = []
    all_modules: List[list] = []
    for plane, dev in sorted(devices.items()):
        ops = dev["ops"]
        s = np.array([o[1] for o in ops], np.int64)
        e = s + np.array([o[2] for o in ops], np.int64)
        us, ue = union_intervals(s, e)
        unions[plane] = (us, ue)
        busy_by_device[plane] = float((ue - us).sum()) / 1e9
        all_ops.extend(ops)
        all_modules.extend(dev.get("modules") or [])
        t_min = int(s.min()) if t_min is None else min(t_min, int(s.min()))
        t_max = int(e.max()) if t_max is None else max(t_max, int(e.max()))
    if host:
        hs = np.array([h[2] for h in host], np.int64)
        he = hs + np.array([h[3] for h in host], np.int64)
    window_s = (t_max - t_min) / 1e9
    n = len(devices)

    first = sorted(devices)[0]
    us, ue = unions[first]
    gap_s = np.concatenate([[t_min], ue])
    gap_e = np.concatenate([us, [t_max]])
    keep = (gap_e - gap_s) >= MIN_GAP_NS
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    longest = np.argsort(-(gap_e - gap_s))[:MAX_GAPS]
    by_span: Dict[str, float] = defaultdict(float)
    if host:
        for i in longest:
            mid = (gap_s[i] + gap_e[i]) // 2
            cover = np.flatnonzero((hs <= mid) & (he >= mid))
            if len(cover):
                inner = cover[np.argmax(hs[cover])]
                name = f"{host[inner][0]}:{host[inner][1]}"
            else:
                name = "no_host_span"
            by_span[name] += float(gap_e[i] - gap_s[i]) / 1e9
    else:
        by_span["no_host_span"] = float((gap_e - gap_s).sum()) / 1e9
    gaps = sorted(([k, v] for k, v in by_span.items()), key=lambda r: -r[1])
    busy_s = sum(busy_by_device.values()) / n
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_by_device": busy_by_device,
        "idle_share": 1.0 - busy_s / window_s,
        "idle_share_worst": 1.0 - min(busy_by_device.values()) / window_s,
        "n_devices": n,
        "ops": _by_name([o for o in all_ops if not o[0].startswith(CONTAINERS)], n),
        "modules": _by_name(all_modules, n),
        "idle_gaps": gaps,
        "idle_gap_total_s": float((gap_e - gap_s).sum()) / 1e9,
    }


def breakdown(reduced: Dict[str, Any]) -> Dict[str, Any]:
    """The ten device ops that took most time and the ten longest idle gaps by
    host span, for the result line."""
    return {
        "device_ops": [[r[0], r[1]] for r in reduced["ops"][:10]],
        "idle_gaps": [[r[0], r[1]] for r in reduced["idle_gaps"][:10]],
    }
