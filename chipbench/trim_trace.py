#!/usr/bin/env python3
"""Cut a profiler trace (``.xplane.pb``) down to what ``trace.View`` reads,
for a small trace the benchmark's tests can keep:

    python3 chipbench/trim_trace.py <trace.xplane.pb> <out.xplane.pb> \
        --decode-runs 4

Kept: the harness's host spans (``bench.*``), and on every device plane
the program executions ("XLA Modules") that started in the window and the
operations ("XLA Ops") of the first prefill and of the first
``--decode-runs`` decode steps in it, each with its name, start and
duration.  Everything else (other lines and planes, the events' stats) is
left out; executions whose operations are left out keep their module
event, so a reader sees fewer operations than a full trace holds only
inside programs it is not given.
"""

from __future__ import annotations

import argparse
import re
import sys


def _esc(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _plane(pid: int, name: str, lines: dict) -> str:
    """Text proto of one XPlane: ``lines`` maps a line name to its
    (name, start_ns, duration_ns) events."""
    meta, out = {}, [f'planes {{ id: {pid} name: "{_esc(name)}"']
    for lid, (line, events) in enumerate(lines.items(), 1):
        out.append(f'  lines {{ id: {lid} name: "{_esc(line)}" '
                   f'timestamp_ns: 0')
        for ev, start, dur in events:
            mid = meta.setdefault(ev, len(meta) + 1)
            out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                       f"{round(start * 1000)} duration_ps: "
                       f"{round(dur * 1000)} }}")
        out.append("  }")
    for ev, mid in meta.items():
        out.append(f'  event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{_esc(ev)}" }} }}')
    out.append("}")
    return "\n".join(out)


def trim(path: str, decode_runs: int) -> bytes:
    """The serialized XSpace of the trimmed trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = [(ev.name, ev.start_ns, ev.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("bench.")]
    window = [s for s in spans if s[0] == "bench.window"][0]
    w0, w1 = window[1], window[1] + window[2]
    planes = [_plane(1, "/host:CPU", {"python3": spans})]
    for plane in pd.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in lines.get("XLA Modules", [])
                      if w0 <= ev.start_ns <= w1)
        keep, decodes, prefill = [], 0, False
        for s, e, name in mods:
            if name.startswith("jit_decode(") and decodes < decode_runs:
                keep.append((s, e))
                decodes += 1
            elif "prefill" in name and not prefill:
                keep.append((s, e))
                prefill = True
        ops = [(ev.name, ev.start_ns, ev.duration_ns)
               for ev in lines.get("XLA Ops", [])
               if any(s <= ev.start_ns <= e for s, e in keep)]
        planes.append(_plane(len(planes) + 1, plane.name, {
            "XLA Modules": [(n, s, e - s) for s, e, n in mods],
            "XLA Ops": ops}))
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--decode-runs", type=int, default=4)
    args = ap.parse_args(argv)
    data = trim(args.trace, args.decode_runs)
    with open(args.out, "wb") as f:
        f.write(data)
    print(f"{args.out}: {len(data)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
