"""From a profiler trace to the numbers the benchmark reports.

`load(logdir)` reads the newest `*.xplane.pb` under a `jax.profiler` log
directory with JAX's own reader and keeps two event lists, each of
(name, start_ns, duration_ns):

  * device: the operations on the accelerator's op line ("XLA Ops" of each
    `/device:TPU:N` plane); on a CPU rehearsal, the XLA CPU worker threads.
    Each event has a fourth element, the index of its plane in
    `device_planes`; an event without one is on plane 0;
  * host: the benchmark's own `TraceAnnotation`s ("commit", "read").

`reduce(...)` turns them into readings of one chip, averaged over the
planes: the busy time (on each plane the union of its op intervals), a
kernel's time and launch count (summed over the planes, so time over
launches is one chip's launch), the device operations that took most
time, and the idle gaps of each plane, each attributed to the host
annotation that covers most of it. With one plane the chip's readings are
the trace's.

The kept lists are plain JSON (`load_events` reads them gzipped), which is
how the tests hold a small recorded trace.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

HOST_NAMES = ("commit", "read")
TOP = 10


def _device_line(plane: str, line: str) -> bool:
    if plane.startswith("/device:TPU"):
        return line == "XLA Ops"
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def op_name(name: str) -> str:
    """`%fusion.2 = f32[...] fusion(...)` -> `fusion.2`: the HLO op's own
    name, without its shapes and operands."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(logdir: str) -> Dict[str, list]:
    import jax
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device, host, planes = [], [], set()
    for plane in data.planes:
        for line in plane.lines:
            dev = _device_line(plane.name, line.name)
            if dev:
                planes.add(plane.name)
            for ev in line.events:
                if dev:
                    device.append((op_name(ev.name), int(ev.start_ns),
                                   int(ev.duration_ns), plane.name))
                elif ev.name in HOST_NAMES:
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)))
    planes = sorted(planes)
    index = {p: i for i, p in enumerate(planes)}
    device = [(n, s, d, index[p]) for n, s, d, p in device]
    return {"device": device, "host": host, "device_planes": planes}


def load_events(path: str) -> Dict[str, list]:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merge intervals; returns (merged starts, merged ends)."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(run_end[idx[1:] - 1], run_end[-1])


def reduce(events: Dict[str, list], kernel: str,
           window_ns: Optional[tuple] = None) -> Dict[str, object]:
    """busy_s, window_s, kernel_s, kernel_launches, device_ops, idle_gaps.

    window_ns: (start, end) of the traced window on the trace's clock;
    by default from the first op's start to the last op's end on any
    plane. Over the planes (`device_planes`, at least those the events
    name): busy_s, device_ops and the "(all gaps)" seconds are means,
    kernel_s and kernel_launches sums, a "(longest gap)" the longest on
    any plane; a plane with no op is idle for the whole window."""
    dev = events["device"]
    if not dev:
        return {"busy_s": 0.0, "window_s": 0.0, "kernel_s": 0.0,
                "kernel_launches": 0, "device_ops": [], "idle_gaps": []}
    names = [e[0] for e in dev]
    start = np.array([e[1] for e in dev], np.int64)
    dur = np.array([e[2] for e in dev], np.int64)
    plane = np.array([e[3] if len(e) > 3 else 0 for e in dev], np.int64)
    end = start + dur
    lo, hi = window_ns if window_ns else (int(start.min()), int(end.max()))
    start, end = np.clip(start, lo, hi), np.clip(end, lo, hi)
    n_dev = max(1, len(events.get("device_planes", [])),
                int(plane.max()) + 1)
    busy_ns = 0
    gap_s, gap_e = [], []
    for p in range(n_dev):
        on = plane == p
        us, ue = _union(start[on], end[on])
        busy_ns += int((ue - us).sum())
        # idle gaps between this plane's merged busy intervals, inside the
        # window
        gs, ge = np.append(lo, ue), np.append(us, hi)
        keep = ge > gs
        gap_s.append(gs[keep])
        gap_e.append(ge[keep])
    pat = re.compile(kernel)
    is_k = np.array([bool(pat.search(n)) for n in names])
    per_op = defaultdict(int)
    for n, d in zip(names, (end - start).tolist()):
        per_op[n] += d
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = _attribute(np.concatenate(gap_s), np.concatenate(gap_e),
                      events.get("host", []), n_dev)
    return {
        "busy_s": busy_ns / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": float((end - start)[is_k].sum()) / 1e9,
        "kernel_launches": int(is_k.sum()),
        "device_ops": [[n, d / n_dev / 1e9] for n, d in ops],
        "idle_gaps": gaps,
    }


def _covered(s: np.ndarray, e: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted intervals [s, e) that lies before t."""
    cum = np.concatenate([[0], np.cumsum(e - s)])
    i = np.searchsorted(s, t, side="right") - 1
    part = np.clip(t - s[np.maximum(i, 0)], 0, (e - s)[np.maximum(i, 0)])
    return np.where(i >= 0, cum[np.maximum(i, 0)] + part, 0)


def _attribute(gs: np.ndarray, ge: np.ndarray, host: List[list],
               n_dev: int = 1):
    """Idle seconds by what the host was doing (the annotation that
    overlaps each gap most, else "none"): the total over `n_dev` planes'
    gaps divided by `n_dev`, and the longest single gap of each kind,
    largest first."""
    names = sorted({h[0] for h in host})
    overlap = np.zeros((len(names) + 1, gs.size), np.int64)
    for j, n in enumerate(names, start=1):
        v = np.array([[h[1], h[1] + h[2]] for h in host if h[0] == n],
                     np.int64)
        s, e = _union(v[:, 0], v[:, 1])
        overlap[j] = _covered(s, e, ge) - _covered(s, e, gs)
    best = np.argmax(overlap, axis=0)       # row 0 (all zero): "none"
    labels = ["none"] + names
    length = ge - gs
    rows = []
    for j in np.unique(best):
        sel = length[best == j]
        rows.append([f"{labels[j]} (all gaps)",
                     float(sel.sum()) / n_dev / 1e9])
        rows.append([f"{labels[j]} (longest gap)", float(sel.max()) / 1e9])
    return sorted(rows, key=lambda r: -r[1])[:TOP]
