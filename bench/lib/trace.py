"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per-program device time and executions, the time of the
Pallas kernels (``tpu_custom_call`` ops) inside each program, the union
of device busy intervals, the host spans on the same clock, and a short
breakdown of the longest device ops and idle gaps.

Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line holds
one event per program execution, named ``jit_<function>(<hash>)``, and
their ``XLA Ops`` line one event per HLO op, named by its HLO text.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
_OPNAME = re.compile(r"^%?([\w.\-]+)")


def module_base(name: str) -> str:
    """``jit_block_step(1234)`` -> ``jit_block_step``."""
    return _MODULE.match(name).group(1)


def op_base(name: str) -> str:
    """``%fusion.79 = bf16[...] fusion(...)`` -> ``fusion.79``."""
    m = _OPNAME.match(name)
    return m.group(1) if m else name[:60]


def is_kernel(name: str) -> bool:
    """A Pallas kernel: an HLO custom call to ``tpu_custom_call``."""
    return "tpu_custom_call" in name


def union_s(intervals) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


@dataclasses.dataclass
class Program:
    count: int = 0
    device_s: float = 0.0
    kernel_s: float = 0.0     # tpu_custom_call ops inside its executions


@dataclasses.dataclass
class Summary:
    window_s: float           # length of the traced window
    busy_s: float             # union of device op intervals, mean per chip
    chips: int
    programs: dict            # module base name -> Program (all chips)
    spans: dict               # host span name -> [(start_ns, end_ns)]
    top_ops: list             # [("program/op", seconds)], most time first
    idle_gaps: list           # [(host activity, seconds)], longest first

    def program(self, prefix: str) -> Program:
        """Every program whose name starts with ``prefix``, summed."""
        out = Program()
        for name, p in self.programs.items():
            if name.startswith(prefix):
                out.count += p.count
                out.device_s += p.device_s
                out.kernel_s += p.kernel_s
        return out

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {files}")
    return files[0]


def _gap_label(gap, host) -> str:
    """The host event that overlaps the idle gap most (the innermost one
    on a tie): what the host was doing while the device waited."""
    s, e = gap
    best, best_key = "no host event", None
    for name, hs, he in host:
        if he <= s:
            continue
        if hs >= e:
            break
        ov = min(e, he) - max(s, hs)
        key = (ov, -(he - hs))
        if ov > 0 and (best_key is None or key > best_key):
            best, best_key = name, key
    return best


def reduce(path: str, window_s: float) -> Summary:
    """Reduce the trace under ``path`` (a directory or an ``.xplane.pb``)
    over a traced window of ``window_s`` seconds."""
    from jax.profiler import ProfileData

    pb = path if path.endswith(".xplane.pb") else find_xplane(path)
    data = ProfileData.from_file(pb)
    programs: dict = {}
    op_time: dict = {}
    busy, chips = 0.0, 0
    all_busy = []
    host = []
    spans: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, module_base(e.name))
                          for e in lines["XLA Modules"].events) \
                if "XLA Modules" in lines else []
            ops = sorted((e.start_ns, e.end_ns, e.name)
                         for e in lines["XLA Ops"].events) \
                if "XLA Ops" in lines else []
            if not mods and not ops:
                continue
            chips += 1
            for s, e, name in mods:
                p = programs.setdefault(name, Program())
                p.count += 1
                p.device_s += (e - s) / 1e9
            # each op belongs to the program whose execution contains it
            j = 0
            for s, e, name in ops:
                while j < len(mods) and mods[j][1] <= s:
                    j += 1
                inside = j < len(mods) and mods[j][0] <= s
                key = f"{mods[j][2] if inside else '?'}/{op_base(name)}"
                op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
                if inside and is_kernel(name):
                    programs[mods[j][2]].kernel_s += (e - s) / 1e9
            intervals = [(s, e) for s, e, *_ in ops] or \
                [(s, e) for s, e, _ in mods]
            busy += union_s(intervals)
            all_busy.extend(intervals)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    host.append((e.name, e.start_ns, e.end_ns))
                    if "/" in e.name and not e.name.startswith("$"):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    host.sort(key=lambda h: h[1])
    # idle gaps between device activity (all chips together)
    merged = []
    for s, e in sorted(all_busy):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    gaps = sorted(((merged[i][1], merged[i + 1][0])
                   for i in range(len(merged) - 1)),
                  key=lambda g: g[0] - g[1])[:10]
    idle = [(_gap_label(g, host), (g[1] - g[0]) / 1e9) for g in gaps]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return Summary(window_s=float(window_s),
                   busy_s=busy / max(chips, 1), chips=chips,
                   programs=programs, spans=spans, top_ops=top,
                   idle_gaps=idle)
