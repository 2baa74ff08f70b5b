#!/usr/bin/env python3
"""Device time by named scope, from a profiler capture (``.xplane.pb``).

``jax.named_scope`` marks ``tower``, ``projector``, ``splice``,
``prefill_attn``, ``decode_attn``, ``mlp``, ``lm_head`` and ``sample`` in the
model, and in the hybrid decoder ``ssm_scan``, ``ssm_step``, ``moe_route``,
``moe_experts`` and ``moe_shared`` (OBSERVABILITY.md "Profiling"). On a TPU trace the scope path of a
device operation is in the operation's *metadata*: the stat ``tf_op``, for
instance ``jit(_decode_segment)/.../decode_attn/dot_general:``; a fusion
carries its root's. ``jax.profiler.ProfileData`` shows an event's own stats
and not its metadata's, so the device plane is read here from the file's
bytes: the few fields of the protobuf wire format that hold them (interval
arithmetic and line names are ``benchmark/trace_reduce.py``'s).

An executable carries the metadata it was compiled with, and JAX leaves
metadata out of the persistent compilation cache's key: a program loaded
from an entry that an older build wrote shows that build's scopes (none,
before PR 25). Read scopes off a capture whose programs this build compiled.

Usage:
  python scripts/trace_scopes.py <file.xplane.pb | profile dir> [--top 40]
      [--ops <regex of program names>]

prints, for the first device plane, milliseconds of self time (an operation's
time less the operations nested in it) by program and scope; with ``--ops``,
by operation of the programs the regex matches (the operation list that shows
whether a tensor the size of a cache layer is still written).
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, self_ns)

SCOPES = ("tower", "projector", "splice", "prefill_attn", "decode_attn",
          "prefill_attn_window", "decode_attn_window", "attn_gate",
          "ssm_scan", "ssm_step", "moe_route", "moe_experts", "moe_shared",
          "attn", "mlp", "lm_head", "sample")


# -- protobuf wire format: the few fields of XSpace that are read ------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]):
    """One XStat: (name, value); a ``ref_value`` is looked up."""
    name, val = None, None
    for num, wire, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v)
        elif num in (3, 4) and wire == 0:
            val = _signed(v) if num == 4 else v
        elif num == 5:
            val = _text(v)
        elif num == 7:
            val = stat_names.get(v)
    return name, val


def device_plane(raw: bytes) -> Optional[dict]:
    """The first device plane (lowest ordinal): per event metadata id its
    name and stats, and the ``XLA Ops`` / ``XLA Modules`` lines as (metadata
    id, start ns, end ns) rows."""
    best = None
    view = memoryview(raw)
    for num, wire, plane in _fields(view):
        if num != 1 or wire != 2:
            continue
        name = None
        for n2, w2, v in _fields(plane):
            if n2 == 2:
                name = _text(v)
                break
        m = DEVICE_PLANE.match(name or "")
        if m and (best is None or int(m.group(2)) < best[0]):
            best = (int(m.group(2)), plane)
    if best is None:
        return None
    stat_names: Dict[int, str] = {}
    metas, lines = [], []
    for num, wire, v in _fields(best[1]):
        if num == 5:        # map<int64, XStatMetadata>
            for n2, _, entry in _fields(v):
                if n2 == 2:
                    sid, sname = None, ""
                    for n3, _, x in _fields(entry):
                        if n3 == 1:
                            sid = x
                        elif n3 == 2:
                            sname = _text(x)
                    stat_names[sid] = sname
        elif num == 4:      # map<int64, XEventMetadata>
            metas.append(v)
        elif num == 3:
            lines.append(v)
    meta: Dict[int, dict] = {}
    for entry in metas:
        for n2, _, body in _fields(entry):
            if n2 != 2:
                continue
            mid, rec = None, {"name": "", "stats": {}}
            for n3, w3, x in _fields(body):
                if n3 == 1:
                    mid = x
                elif n3 == 2:
                    rec["name"] = _text(x)
                elif n3 == 5:
                    k, val = _stat(x, stat_names)
                    if k:
                        rec["stats"][k] = val
            meta[mid] = rec
    out = {"meta": meta}
    for line in lines:
        lname, t0_ns, events = "", 0, []
        for n2, w2, v in _fields(line):
            if n2 == 2:
                lname = _text(v)
            elif n2 == 3:
                t0_ns = _signed(v)
            elif n2 == 4:
                events.append(v)
        if lname not in (OPS_LINE, MODULES_LINE):
            continue
        rows = np.zeros((len(events), 3), np.int64)
        for i, ev in enumerate(events):
            mid = off = dur = 0
            for n3, w3, v in _fields(ev):
                if n3 == 1:
                    mid = v
                elif n3 == 2:
                    off = v
                elif n3 == 3:
                    dur = v
            start = t0_ns + off // 1000
            rows[i] = (mid, start, start + dur // 1000)
        out[lname] = rows
    return out


def seconds_by_scope(path: str, by_op: bool = False
                     ) -> Dict[Tuple[str, ...], float]:
    """(program, scope) -> seconds of device self time; with ``by_op``,
    (program, scope, operation). The program is the XLA module's name
    without its fingerprint; the scope is the first part of the operation's
    ``tf_op`` that is one of ``SCOPES``, or ``-``."""
    with open(path, "rb") as f:
        dev = device_plane(f.read())
    if dev is None or OPS_LINE not in dev:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} line")
    programs = {}
    for rec in dev["meta"].values():
        m = re.match(r"^(.*)\((\d+)\)$", rec["name"].strip())
        if m:
            programs[int(m.group(2))] = m.group(1)
    rows = dev[OPS_LINE]
    out: Dict[Tuple[str, ...], float] = collections.defaultdict(float)
    for (mid, _, _), ns in zip(rows, self_ns(rows[:, 1:3])):
        rec = dev["meta"].get(int(mid), {})
        stats = rec.get("stats", {})
        parts = (stats.get("tf_op") or "").split("/")
        scope = next((p for p in parts if p in SCOPES), "-")
        key = (programs.get(stats.get("program_id"), "?"), scope)
        out[key + (rec.get("name", ""),) if by_op else key] += ns / 1e9
    return dict(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--ops", default="",
                    help="list operations of the programs this regex matches")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            sys.stderr.write(f"no .xplane.pb under {path}\n")
            return 2
        path = found[-1]
    if args.ops:
        table = {k: s for k, s in seconds_by_scope(path, by_op=True).items()
                 if re.search(args.ops, k[0])}
        print(f"{path}: device self time by operation of /{args.ops}/")
        for (_, scope, op), s in sorted(
                table.items(), key=lambda kv: -kv[1])[:args.top]:
            print(f"  {s * 1e3:11.3f} ms  {scope:13s} {op[:150]}")
        return 0
    table = seconds_by_scope(path)
    by_program = collections.defaultdict(float)
    for (prog, _), s in table.items():
        by_program[prog] += s
    print(f"{path}: device self time by program and scope")
    for (prog, scope), s in sorted(table.items(), key=lambda kv: -kv[1])[:args.top]:
        print(f"  {prog:36s} {scope:13s} {s * 1e3:11.3f} ms "
              f"{100 * s / by_program[prog]:5.1f} % of the program")
    return 0


if __name__ == "__main__":
    sys.exit(main())
