"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

``reduce_file`` reads the planes of one trace: a device plane is named
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per run of a
compiled program (named after the jitted function), its ``XLA Ops`` line one
event per operation inside them. Busy time is the union of the operations'
intervals (of the programs' where a trace has no operation line), averaged
over the device planes. Idle gaps are the complement inside the traced
window, each labelled with the host event (a thread's frame or a
``TraceAnnotation`` such as ``serve.segment_dispatch``) that overlaps it
most.

The reduction is code, kept with the benchmark and checked on a small
recorded trace (``tests/benchmark_suite``), so every PR computes the same
number the same way.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_HOST_EVENT_NS = 20_000


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_ns(intervals: np.ndarray) -> Tuple[int, np.ndarray]:
    """Total length of the union of [start, end) rows, and the merged rows."""
    if not len(intervals):
        return 0, np.zeros((0, 2), np.int64)
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    last = np.concatenate([np.flatnonzero(new)[1:] - 1, [len(iv) - 1]])
    merged = np.stack([starts, ends[last]], 1)
    return int((merged[:, 1] - merged[:, 0]).sum()), merged


def self_ns(iv: np.ndarray) -> np.ndarray:
    """For each [start, end) row, its length less the rows nested directly
    inside it (a ``while`` spans the operations of its body): the time that
    is the operation's own."""
    own = (iv[:, 1] - iv[:, 0]).astype(np.int64)
    order = np.lexsort((-iv[:, 1], iv[:, 0]))
    stack = []
    for i in order:
        while stack and iv[stack[-1], 1] <= iv[i, 0]:
            stack.pop()
        if stack and iv[i, 1] <= iv[stack[-1], 1]:
            own[stack[-1]] -= iv[i, 1] - iv[i, 0]
        stack.append(i)
    return np.maximum(own, 0)


def module_name(raw: str) -> str:
    """``jit__prefill_jit(1234567)`` -> ``jit__prefill_jit``."""
    return re.sub(r"\(\d+\)$", "", raw.strip())


def _events(line) -> Tuple[List[str], np.ndarray]:
    names, iv = [], []
    for ev in line.events:
        names.append(ev.name)
        start = int(ev.start_ns)
        iv.append((start, start + int(ev.duration_ns)))
    return names, np.asarray(iv, np.int64).reshape(-1, 2)


def reduce_file(path: str, window_s: Optional[float] = None,
                n_chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        if m:
            devices.append((int(m.group(2)), lines))
        elif plane.name.startswith("/host:"):
            host.append(lines)
    devices.sort(key=lambda d: d[0])
    devices = devices[:max(n_chips, 1)]
    if not devices:
        raise ValueError(f"{path}: no device plane (planes: "
                         f"{[p.name for p in pd.planes]})")

    busy_ns, lo, hi = [], None, None
    op_time: Dict[str, int] = defaultdict(int)
    op_count: Dict[str, int] = defaultdict(int)
    mod_time: Dict[str, List[int]] = defaultdict(list)
    gaps_src = None
    for _, lines in devices:
        src = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if src is None:
            continue
        names, iv = _events(src)
        total, merged = union_ns(iv)
        busy_ns.append(total)
        if len(iv):
            lo = iv[:, 0].min() if lo is None else min(lo, iv[:, 0].min())
            hi = iv[:, 1].max() if hi is None else max(hi, iv[:, 1].max())
        if gaps_src is None:
            gaps_src = merged
        if OPS_LINE in lines:
            for n, own in zip(names, self_ns(iv)):
                op_time[n] += int(own)
                op_count[n] += 1
        if MODULES_LINE in lines:
            mnames, miv = _events(lines[MODULES_LINE])
            for n, (a, b) in zip(mnames, miv):
                mod_time[module_name(n)].append(int(b - a))
    if not busy_ns or lo is None:
        raise ValueError(f"{path}: no operation ran on the device")
    span_s = (hi - lo) / 1e9
    window = float(window_s) if window_s else span_s
    window = max(window, span_s)
    busy_s = float(np.mean(busy_ns)) / 1e9

    # Idle gaps of the first device, longest first, labelled by the host.
    gaps = []
    if gaps_src is not None and len(gaps_src) > 1:
        g = np.stack([gaps_src[:-1, 1], gaps_src[1:, 0]], 1)
        order = np.argsort(g[:, 0] - g[:, 1])[:10]
        hnames, hiv = [], []
        for lines in host:
            for lname, ln in lines.items():
                ns, iv = _events(ln)
                keep = (iv[:, 1] - iv[:, 0]) >= MIN_HOST_EVENT_NS if len(iv) else []
                for n, row, k in zip(ns, iv, keep):
                    if k:
                        hnames.append(f"{lname}:{n}")
                        hiv.append(row)
        hiv = np.asarray(hiv, np.int64).reshape(-1, 2)
        for i in order:
            a, b = g[i]
            label = "host: nothing recorded"
            if len(hiv):
                ov = np.minimum(hiv[:, 1], b) - np.maximum(hiv[:, 0], a)
                # Prefer the shortest event that covers most of the gap: the
                # innermost frame, not the thread's outermost loop.
                cover = ov / max(b - a, 1)
                ok = np.flatnonzero(cover >= 0.5)
                j = (ok[np.argmin((hiv[ok, 1] - hiv[ok, 0]))] if len(ok)
                     else int(np.argmax(ov)))
                if ov[j] > 0:
                    label = hnames[j]
            gaps.append([label[:120], float(b - a) / 1e9])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_s,
        "window_s": window,
        "span_s": span_s,
        "n_device_planes": len(devices),
        "modules": {n: {"runs": len(v), "total_s": sum(v) / 1e9,
                        "median_s": float(np.median(v)) / 1e9}
                    for n, v in mod_time.items()},
        "ops": {n: {"runs": op_count[n], "total_s": t / 1e9}
                for n, t in op_time.items()},
        "breakdown": {
            "device_ops": [[n[:120], t / 1e9] for n, t in top_ops],
            "idle_gaps": gaps,
        },
    }


def reduce_dir(trace_dir: str, run=None, window_s: Optional[float] = None) -> dict:
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(path, window_s=window_s,
                       n_chips=getattr(run, "n_chips", 1))
