"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

The window is the host span ``bench.window`` that the driver opens and
closes.  On each device plane (``/device:TPU:<n>``) the line "XLA Modules"
holds one event per execution of a compiled program, named after the
jitted function (``jit_decode(<id>)``), and the line "XLA Ops" one event
per operation executed, named by its HLO text
(``%_flash_attention.6 = bf16[...] custom-call(...), ...``).  An operation
belongs to the program execution whose interval holds its start; its
short name is the instruction's name without ``%`` and the trailing
number (``_flash_attention``).  A Pallas kernel is an operation whose text
names ``custom_call_target="tpu_custom_call"``; its short name is that of
the jitted wrapper that called it.  Control flow (``while``, ``call``,
``conditional``) spans the operations of its body and is left out.

  busy_s     union of the operation intervals inside the window, averaged
             over the chips used
  window_s   the window's length
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Optional

MODULES, OPS = "XLA Modules", "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
CONTROL_FLOW = re.compile(r"^(while|call|conditional)$")


@dataclasses.dataclass
class Op:
    name: str               # short name: "_flash_attention", "copy"
    label: str              # instruction name: "_flash_attention.6"
    start: float            # ns
    end: float
    module: str             # name of the program execution that holds it
    kernel: bool


@dataclasses.dataclass
class Run:
    """One execution of a compiled program on one device."""
    name: str
    start: float
    end: float
    ops: list

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def module_name(raw: str) -> str:
    """``jit_decode(1234)`` -> ``jit_decode``."""
    return raw.split("(")[0].strip()


def op_label(text: str) -> str:
    """``%copy.73 = bf16[...] copy(...)`` -> ``copy.73``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def op_name(label: str) -> str:
    """``_flash_attention.6`` -> ``_flash_attention``."""
    return re.sub(r"\.\d+$", "", label)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class View:
    """What one traced window holds, per device, with the cell's facts."""

    def __init__(self, runs: dict, spans: list, window, *, cell=None,
                 facts=None, peaks=None, work=None, chips: int = 1):
        self.runs = runs              # device plane -> [Run], in time order
        self.spans = spans            # host spans: (name, start, end)
        self.window = window          # (start, end) ns
        self.cell = cell
        self.facts = facts or {}
        self.peaks = peaks or {}
        self.work = work              # the configuration's flops/<name>.py
        self.chips = chips

    # -- loading ----------------------------------------------------------
    @classmethod
    def load(cls, path: str, **kw) -> "View":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path), **kw)

    @classmethod
    def from_profile(cls, pd, **kw) -> "View":
        runs, spans = {}, []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                runs[plane.name] = _device_runs(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            spans.append((ev.name, ev.start_ns, ev.end_ns))
        windows = [s for s in spans if s[0] == "bench.window"]
        if windows:
            window = (windows[0][1], windows[0][2])
        else:                         # no host span: the whole trace
            ends = [(r.start, r.end) for rs in runs.values() for r in rs]
            window = (min(s for s, _ in ends), max(e for _, e in ends))
        chips = kw.pop("chips", max(len(runs), 1))
        return cls(runs, sorted(spans, key=lambda s: s[1]), window,
                   chips=chips, **kw)

    # -- device time --------------------------------------------------------
    def _planes(self):
        return sorted(self.runs)[:self.chips]

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, plane: str):
        w0, w1 = self.window
        ivs = [(max(o.start, w0), min(o.end, w1))
               for r in self.runs[plane] for o in r.ops
               if o.end > w0 and o.start < w1]
        return _union(ivs)

    def busy_s(self) -> float:
        planes = self._planes()
        if not planes:
            return 0.0
        total = sum(e - s for p in planes for s, e in self.busy_intervals(p))
        return total * 1e-9 / len(planes)

    def idle_share(self) -> Optional[float]:
        w = self.window_s()
        if w <= 0 or not self._planes():
            return None
        return 1.0 - self.busy_s() / w

    def runs_of(self, pattern: str) -> list:
        """Executions of programs whose name matches ``pattern`` (first
        chip) that started once the window had opened: the work that the
        window dispatched, which the trace holds to its end."""
        planes = self._planes()
        if not planes:
            return []
        rx = re.compile(pattern)
        return [r for r in self.runs[planes[0]]
                if rx.search(r.name) and r.start >= self.window[0]]

    @staticmethod
    def kernel_seconds(runs, pattern: str) -> float:
        """Device seconds of the Pallas kernels whose short name matches
        ``pattern`` inside ``runs``."""
        rx = re.compile(pattern)
        return sum(o.end - o.start for r in runs for o in r.ops
                   if o.kernel and rx.search(o.name)) * 1e-9

    def least_s(self, ops: float, byts: float) -> float:
        """The least time the chip could take for ``ops`` operations that
        move ``byts`` bytes: its roofline."""
        return max(ops / self.peaks["bf16_flops_per_s"],
                   byts / self.peaks["hbm_bytes_per_s"])

    # -- breakdown ----------------------------------------------------------
    def host_span_at(self, t: float) -> str:
        """The innermost harness span open at ``t``."""
        best = "none"
        best_len = None
        for name, s, e in self.spans:
            if s <= t <= e and name != "bench.window":
                if best_len is None or e - s < best_len:
                    best, best_len = name, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        planes = self._planes()
        if not planes:
            return {"device_ops": [], "idle_gaps": []}
        plane = planes[0]
        w0, w1 = self.window
        per_op = collections.Counter()
        for r in self.runs[plane]:
            for o in r.ops:
                if o.end > w0 and o.start < w1:
                    per_op[f"{r.name}/{o.label}"] += (o.end - o.start) * 1e-9
        busy = self.busy_intervals(plane)
        gaps, prev = [], w0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if w1 > prev:
            gaps.append((prev, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return {
            "device_ops": [[n, s] for n, s in per_op.most_common(top)],
            "idle_gaps": [[self.host_span_at((s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps[:top]],
        }


def _device_runs(plane) -> list:
    modules, ops = [], []
    for line in plane.lines:
        if line.name == MODULES:
            modules = [(ev.start_ns, ev.end_ns, module_name(ev.name))
                       for ev in line.events]
        elif line.name == OPS:
            ops = list(line.events)
    modules.sort()
    starts = [m[0] for m in modules]
    runs = [Run(name=n, start=s, end=e, ops=[]) for s, e, n in modules]
    for ev in ops:
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i < 0 or ev.start_ns > runs[i].end:
            continue
        label = op_label(ev.name)
        name = op_name(label)
        if CONTROL_FLOW.match(name):
            continue
        runs[i].ops.append(Op(name=name, label=label, start=ev.start_ns,
                              end=ev.end_ns, module=runs[i].name,
                              kernel=KERNEL_MARK in ev.name))
    return runs
