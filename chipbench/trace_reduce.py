"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* The window is the host span named ``chipbench.window``, which the harness
  opens when the measured window opens and closes when it closes.
* Device time comes from each TPU plane's ``XLA Ops`` line, whose events
  are named by their whole HLO instruction: busy time is the union of the op
  intervals inside the window, averaged over the chips.
* Pallas kernels are the ops whose instruction is a ``tpu_custom_call``;
  their time is summed per chip, and per compiled program (the ``XLA
  Modules`` event that holds them) with the HBM bytes their operands and
  results occupy (shapes outside HBM, memory space ``S(1)``, do not count).
  Within a program the same sums are kept per kernel family, the
  instruction name less its ``.N`` suffix (``lscd_spmm_splitk_grouped``),
  so that one kernel's roofline can be read apart from the others in the
  same program.
* The op table names each op by its instruction name and leaves out the
  control-flow ops (``while``, ``conditional``, ``call``) that contain
  others.
* The idle gaps (window minus busy) are attributed to what the host was
  doing at each gap's midpoint: the innermost ``chipbench.*`` span open then
  (``chipbench.step`` inside ``server.step()``, ``chipbench.submit``,
  ``chipbench.wait`` while the generator sleeps), else ``host``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
TOP = 10


def find_trace(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


PALLAS = 'custom_call_target="tpu_custom_call"'
CONTAINERS = (" while(", " conditional(", " call(")
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\.\d+$")


def is_pallas(name: str) -> bool:
    return PALLAS in name


def op_name(name: str) -> str:
    """``%lscd_spmm.16 = bf16[...] custom-call(...)`` -> ``lscd_spmm.16``."""
    return name.split(" = ", 1)[0].strip().removeprefix("ROOT ").lstrip("%")


def family(name: str) -> str:
    """``%lscd_spmm.16 = ...`` -> ``lscd_spmm``: the kernel, not the call."""
    return _SUFFIX.sub("", op_name(name))


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, w0: float, w1: float):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def reduce_planes(planes) -> Optional[Dict]:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events``, each event with ``name``, ``start_ns``, ``duration_ns``
    and ``stats``), as ``jax.profiler.ProfileData`` gives them. Returns
    None when the trace holds no window or no device op."""
    host_spans: List[Tuple[float, float, str]] = []
    window = None
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(PREFIX):
                        continue
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (s, e)
                    else:
                        host_spans.append((s, e, ev.name))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            if OPS_LINE in lines:
                devices.append((lines[OPS_LINE],
                                lines.get(MODULES_LINE, [])))
    if window is None or not devices:
        return None
    w0, w1 = window
    busy_total = pallas_total = 0.0
    per_op: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    gaps: List[Tuple[float, float]] = []
    for ops, modules in devices:
        runs = sorted((m.start_ns, m.start_ns + m.duration_ns, m.name)
                      for m in modules)
        starts = [r[0] for r in runs]
        for s, e, name in runs:
            if _clip(s, e, w0, w1):
                k = kernels.setdefault(name, {"runs": 0, "seconds": 0.0,
                                              "hbm_bytes": 0,
                                              "families": {}})
                k["runs"] += 1
        iv = []
        for ev in ops:
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if c is None:
                continue
            iv.append(c)
            dur = (c[1] - c[0]) * 1e-9
            if not any(x in ev.name for x in CONTAINERS):
                key = op_name(ev.name)
                per_op[key] = per_op.get(key, 0.0) + dur
            if is_pallas(ev.name):
                pallas_total += dur
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                if i >= 0 and runs[i][1] >= ev.start_ns and runs[i][2] in \
                        kernels:
                    k = kernels[runs[i][2]]
                    nbytes = hbm_bytes(ev.name)
                    k["seconds"] += dur
                    k["hbm_bytes"] += nbytes
                    f = k["families"].setdefault(family(ev.name), {
                        "calls": 0, "seconds": 0.0, "hbm_bytes": 0})
                    f["calls"] += 1
                    f["seconds"] += dur
                    f["hbm_bytes"] += nbytes
        merged = union(iv)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        prev = w0
        for s, e in merged + [(w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    n = len(devices)
    host_spans.sort(key=lambda x: x[0])

    def doing(t: float) -> str:
        best = None
        for s, e, name in host_spans:
            if s <= t < e and (best is None or s >= best[0]):
                best = (s, name)
        return best[1][len(PREFIX):] if best else "host"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "n_devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n,
        "pallas_s": pallas_total / n,
        "device_ops": [[k, v / n] for k, v in sorted(
            per_op.items(), key=lambda kv: kv[1], reverse=True)[:TOP]],
        "idle_gaps": [[doing((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:TOP]],
        "kernels": {k: v for k, v in kernels.items() if v["seconds"] > 0},
    }


def load_planes(path: str) -> list:
    import jax
    return list(jax.profiler.ProfileData.from_file(path).planes)


def reduce_file(path: str) -> Optional[Dict]:
    return reduce_planes(load_planes(path))


# Bytes of one element of each HLO type.
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\](\{[^}]*\})?")


def hbm_bytes(instruction: str) -> int:
    """Bytes of the result and operands of one HLO instruction (as the
    trace names an op) that live in HBM: every shape except those whose
    layout puts them in memory space 1 (``S(1)``, on-chip)."""
    head, _, rest = instruction.partition(" = ")
    body = rest.split(", custom_call_target=")[0]
    total = 0
    for dtype, dims, layout in _SHAPE.findall(body):
        if dtype not in _DTYPE_BYTES or "S(1)" in layout:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total
