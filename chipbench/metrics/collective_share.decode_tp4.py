"""collective_share.decode_tp4: the share of the decode program's device
time on the first chip in which a collective runs, in %: the union of the
intervals of its collective ops over the decode program's device time.

A collective op is one whose short name (``trace.View``: the instruction's
name without its number) is ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute`` or ``all-to-all``, with its
``-start`` / ``-done`` forms; an instruction that JAX names keeps JAX's
spelling (``reduce_scatter`` from ``lax.psum_scatter``).  The TPU compiler
also runs a collective as an async pair of fusions,
``async-collective-start`` / ``async-collective-done``, counted here; a
collective fused into a fusion of another name (``fusion``) cannot be
told from compute by its name and is not counted.  In granite-20b's decode
program on a v5e 2x2 that is two pieces of the gather of q in each layer,
about 8 us of a 408-us layer (``tests/test_tp4_trace.py``).
"""

import re

from chipbench import trace

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|reduce_scatter|"
    r"collective-permute|all-to-all|async-collective)(-start|-done)?$")


def read(view):
    runs = view.runs_of(r"^jit_decode$")
    seconds = sum(r.seconds for r in runs)
    if not runs or seconds <= 0:
        return None
    held = trace._union((o.start, o.end) for r in runs for o in r.ops
                        if COLLECTIVE.match(o.name))
    return 100.0 * sum(e - s for s, e in held) * 1e-9 / seconds
