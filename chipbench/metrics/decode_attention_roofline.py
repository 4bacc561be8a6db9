"""decode_attention_roofline: the decode step's attention kernel (the
Pallas kernels named ``*decode_attention*`` inside the decode program)
against the live K/V bytes and the operations of one query per sequence
from shapes (the work model's ``decode_attention``), in % of its
roofline."""


def read(view):
    count = getattr(view.work, "decode_attention", None)
    runs = view.runs_of(r"^jit_decode$")
    lives = view.facts["decode_live"]
    seconds = view.kernel_seconds(runs, "decode_attention")
    if (count is None or not runs or len(runs) != len(lives)
            or seconds <= 0):
        return None
    B = view.facts["shapes"]["batch"]
    least = sum(view.least_s(*count(view.cell.config, B, live))
                for live in lives)
    return 100.0 * least / seconds
