"""mfu.prefill: the model operations of every prefill in the traced window
(from shapes, by the configuration's work model ``flops/<name>.py``) over
the prefill program's device time and the chip's peak, in %."""


def read(view):
    runs = view.runs_of(r"prefill")
    if not runs:
        return None
    sh = view.facts["shapes"]
    ops = view.work.prefill(view.cell.config, sh["batch"], sh["prompt_len"])
    seconds = sum(r.seconds for r in runs)
    return 100.0 * ops * len(runs) / seconds / view.peaks["bf16_flops_per_s"]
