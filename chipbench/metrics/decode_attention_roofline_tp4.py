"""decode_attention_roofline_tp4: decode attention on the first chip of a
cache whose positions are split over ``tensor_parallel`` chips, in % of
its roofline: the least time for every query head against the live K/V
positions that chip holds (the work model's ``decode_attention`` at
``held``) over the device time, in the decode program on that chip, of
the ops that read that K/V from HBM and attend over it.

Those ops are the Pallas kernels named ``*decode_attention*`` and the
fusions that slice each layer's K and V out of the stacked cache and lay
them out for the kernel: on a TPU v5e the compiler names them
``constant_dynamic-slice_fusion`` and ``copy_bitcast_fusion``, one of
each for K and one for V in every layer of granite-20b's decode program
(``tests/test_tp4_trace.py``), and hands the kernel its operands in VMEM,
so that the kernel's time alone is shorter than the cache's read at HBM
bandwidth.  A program that slices the cache under other names reads
above 100% here.  The relayout of the whole stacked cache once a step
(``copy``) cannot be told from other copies by its name and is not
counted."""

import re

READS_CACHE = re.compile(
    r"^(constant_dynamic-slice_fusion|copy_bitcast_fusion)$")


def read(view):
    count = getattr(view.work, "decode_attention", None)
    runs = view.runs_of(r"^jit_decode$")
    lives = view.facts["decode_live"]
    seconds = view.kernel_seconds(runs, "decode_attention") + sum(
        o.end - o.start for r in runs for o in r.ops
        if READS_CACHE.match(o.name)) * 1e-9
    if (count is None or not runs or len(runs) != len(lives)
            or seconds <= 0):
        return None
    B, C = view.facts["shapes"]["batch"], view.facts["shapes"]["capacity"]
    conf = view.cell.config
    least = sum(view.least_s(*count(conf, B, view.work.held(conf, C, live)))
                for live in lives)
    return 100.0 * least / seconds
