"""The model step's scopes in a profiler trace (``.xplane.pb``).

The program names its parts with ``jax.named_scope``: ``embed``, ``layers``
(each group's layer scan), ``attention``, ``mlp``, ``ssm``,
``cache_update`` (every write into the K/V cache) and ``logits``.  XLA
keeps each operation's JAX name stack in its per-op event metadata, as the
``tf_op`` stat (``jit(decode)/layers/while/body/dynamic_slice:...``), and
the file:line that made it as the ``source`` stat.  ``ProfileData`` does
not expose event metadata, so ``op_metadata`` reads it from the raw XSpace
with a reader of the protobuf wire format: only the device planes'
``event_metadata`` and ``stat_metadata``; the planes' lines are skipped by
their length.

``ScopedView`` is ``trace.View`` whose operations also carry ``scope`` and
``source``.  An operation takes the metadata of its own program: the key is
the program id that names the program execution (``jit_decode(1234)``) and
the operation's instruction name, which two programs may share.

  cache_share    share of the decode program's device time under
                 ``cache_update`` or in the layer scan outside ``attention``,
                 ``mlp`` and ``ssm`` (the scan's slicing of each layer's K and
                 V out of the stacked cache), in %; None where no operation
                 of the decode program carries one of the scopes
  unscoped_share share of a program's device time under none of the scopes

    python3 chipbench/scopes.py <trace.xplane.pb>

prints these, each outermost scope's share of the decode and prefill
programs, and the seconds the metadata pass took, as one JSON line.
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys
import time
from pathlib import Path
from typing import Optional

if __package__ in (None, ""):               # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import trace  # noqa: E402

SCOPES = ("embed", "layers", "attention", "mlp", "ssm", "cache_update",
          "logits")
LAYER_WORK = {"attention", "mlp", "ssm"}
PROGRAMS = {"decode": r"^jit_decode$", "prefill": r"^jit_prefill_step$"}


@dataclasses.dataclass
class ScopedOp(trace.Op):
    scope: str = ""         # JAX name stack: "jit(decode)/layers/while/..."
    source: str = ""        # "<file>:<line>" that made it


def program_id(raw: str) -> Optional[int]:
    """``jit_decode(1234)`` -> 1234, the id its operations' metadata carry."""
    m = re.search(r"\((\d+)\)\s*$", raw)
    return int(m.group(1)) if m else None


def parts(scope: str) -> set:
    """The scope names in a name stack."""
    return set(scope.split("/")) & set(SCOPES)


def moves_cache(scope: str) -> bool:
    p = set(scope.split("/"))
    return "cache_update" in p or ("layers" in p and not p & LAYER_WORK)


class ScopedView(trace.View):
    """``trace.View`` whose operations are ``ScopedOp``."""

    @classmethod
    def load(cls, path: str, **kw) -> "ScopedView":
        from jax.profiler import ProfileData
        data = Path(path).read_bytes()
        pd = ProfileData.from_serialized_xspace(data)
        view = cls.from_profile(pd, **kw)
        view.attach(pd, op_metadata(data))
        return view

    def attach(self, pd, meta: dict) -> None:
        """Gives every operation the scope and source that ``meta`` (the
        ``op_metadata`` of the same trace) holds for it."""
        for plane in pd.planes:
            if plane.name not in self.runs:
                continue
            ids = {(ev.start_ns, ev.end_ns): program_id(ev.name)
                   for line in plane.lines if line.name == trace.MODULES
                   for ev in line.events}
            table = meta.get(plane.name, {})
            for run in self.runs[plane.name]:
                pid = ids.get((run.start, run.end))
                scoped = []
                for o in run.ops:
                    scope, source = table.get((pid, o.label), ("", ""))
                    scoped.append(ScopedOp(**vars(o), scope=scope,
                                           source=source))
                run.ops = scoped

    @staticmethod
    def scope_seconds(runs, predicate) -> float:
        """Device seconds of the operations inside ``runs`` whose scope
        satisfies ``predicate``."""
        return sum(o.end - o.start for r in runs for o in r.ops
                   if predicate(o.scope)) * 1e-9


def cache_share(view: ScopedView) -> Optional[float]:
    runs = view.runs_of(PROGRAMS["decode"])
    if not any(parts(o.scope) for r in runs for o in r.ops):
        return None
    return (100.0 * view.scope_seconds(runs, moves_cache)
            / sum(r.seconds for r in runs))


def unscoped_share(view: ScopedView, program: str) -> Optional[float]:
    """In % of the op time of the program (control flow is left out)."""
    runs = view.runs_of(PROGRAMS[program])
    total = view.scope_seconds(runs, lambda s: True)
    if not total:
        return None
    return 100.0 * view.scope_seconds(runs, lambda s: not parts(s)) / total


def scope_shares(view: ScopedView, program: str) -> dict:
    """Outermost scope -> share of the program's op time, in %."""
    runs = view.runs_of(PROGRAMS[program])
    total = view.scope_seconds(runs, lambda s: True)
    out = {}
    for name in SCOPES:
        def outermost(s, name=name):
            found = [p for p in s.split("/") if p in SCOPES]
            return bool(found) and found[0] == name
        seconds = view.scope_seconds(runs, outermost)
        if seconds:
            out[name] = 100.0 * seconds / total
    return out


# ---------------------------------------------------------------------------
# Op metadata from the raw XSpace (protobuf wire format)
# ---------------------------------------------------------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto
XSPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2
EVENT_META_NAME, EVENT_META_STATS = 2, 5
STAT_META_NAME = 2
STAT_META_ID, STAT_UINT64, STAT_INT64, STAT_STR, STAT_REF = 1, 3, 4, 5, 7
VARINT, FIXED64, LENGTH, FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one message in ``buf[start:end]``: an int
    for a varint, (start, end) of the bytes for a length-delimited field;
    fixed-width fields are skipped."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == VARINT:
            value, i = _varint(buf, i)
        elif kind == LENGTH:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (FIXED64, FIXED32):
            i += 8 if kind == FIXED64 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    for span in entries:
        for f, v in _fields(buf, *span):
            if f == MAP_VALUE:
                yield v


def op_metadata(data: bytes) -> dict:
    """Device plane name -> {(program id, instruction name): (scope,
    source)}.  ``scope`` is the ``tf_op`` stat without its trailing
    ``:<type>``, ``source`` the ``source`` stat; either is "" where it is
    absent."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != XSPACE_PLANES:
            continue
        name, events, stats = "", [], []
        for pf, v in _fields(buf, *plane):
            if pf == PLANE_NAME:
                name = _text(buf, v)
            elif pf == PLANE_EVENT_METADATA:
                events.append(v)
            elif pf == PLANE_STAT_METADATA:
                stats.append(v)
        if trace.DEVICE_PLANE.match(name):
            out[name] = _plane_metadata(buf, events, stats)
    return out


def _plane_metadata(buf, events, stats) -> dict:
    stat_names = {}                     # stat metadata id -> name
    for v in _map_values(buf, stats):
        sid, sname = None, ""
        for f, x in _fields(buf, *v):
            if f == STAT_META_ID:
                sid = x
            elif f == STAT_META_NAME:
                sname = _text(buf, x)
        stat_names[sid] = sname
    wanted = {"tf_op", "source", "program_id"}
    table = {}
    for v in _map_values(buf, events):
        text, found = "", {}
        for f, x in _fields(buf, *v):
            if f == EVENT_META_NAME:
                text = _text(buf, x)
            elif f == EVENT_META_STATS:
                stat, value = _stat(buf, x, stat_names)
                if stat in wanted:
                    found[stat] = value
        scope = str(found.get("tf_op", ""))
        if ":" in scope:                # "<name stack>:<op type>"
            scope = scope.rpartition(":")[0]
        table[(found.get("program_id"), trace.op_label(text))] = (
            scope, str(found.get("source", "")))
    return table


def _stat(buf, span, stat_names):
    """(stat name, value) of one XStat; a reference names another stat
    metadata, whose name is the value."""
    name, value = "", None
    for f, x in _fields(buf, *span):
        if f == STAT_META_ID:
            name = stat_names.get(x, "")
        elif f in (STAT_UINT64, STAT_INT64):
            value = x
        elif f == STAT_STR:
            value = _text(buf, x)
        elif f == STAT_REF:
            value = stat_names.get(x, "")
    return name, value


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    path = (argv if argv is not None else sys.argv[1:])[0]
    data = Path(path).read_bytes()
    pd = ProfileData.from_serialized_xspace(data)
    view = ScopedView.from_profile(pd)
    t0 = time.perf_counter()
    meta = op_metadata(data)
    metadata_s = time.perf_counter() - t0
    view.attach(pd, meta)
    print(json.dumps({
        "trace_bytes": len(data), "metadata_s": metadata_s,
        "cache_share.decode": cache_share(view),
        "unscoped_share": {p: unscoped_share(view, p) for p in PROGRAMS},
        "scope_shares": {p: scope_shares(view, p) for p in PROGRAMS}}))
    return 0

if __name__ == "__main__":
    sys.exit(main())
