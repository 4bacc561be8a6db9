"""roofline_mfu.decode: the whole decode step's share of its roofline, in %:
the least time the chip could take for the traced window's decode steps,
each max(operations / peak, bytes / bandwidth) from shapes at its live
cache length (the configuration's work model ``flops/<name>.py``), over
the decode program's device time."""


def read(view):
    runs = view.runs_of(r"^jit_decode$")
    lives = view.facts["decode_live"]
    if not runs or len(runs) != len(lives):
        return None
    B = view.facts["shapes"]["batch"]
    least = sum(view.least_s(*view.work.decode(view.cell.config, B, live))
                for live in lives)
    return 100.0 * least / sum(r.seconds for r in runs)
