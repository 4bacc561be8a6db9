"""roofline_mfu.decode_tp4: a tensor-parallel decode step's share of one
chip's roofline, in %: the least time the first chip could take for its
work in the traced window's decode steps, each max(operations / peak,
bytes / bandwidth) from shapes at its live cache length (the
configuration's work model counts the first chip's work in a layout split
over ``tensor_parallel`` chips), over the decode program's device time on
that chip."""


def read(view):
    runs = view.runs_of(r"^jit_decode$")
    lives = view.facts["decode_live"]
    if not runs or len(runs) != len(lives):
        return None
    sh = view.facts["shapes"]
    least = sum(view.least_s(*view.work.decode(
        view.cell.config, sh["batch"], sh["capacity"], live))
        for live in lives)
    return 100.0 * least / sum(r.seconds for r in runs)
