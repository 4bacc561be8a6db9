"""flash_attention_roofline: the prefill's attention kernel (the Pallas
kernels named ``*flash_attention*`` inside the prefill program) against
causal attention's operations and its q, k, v, o bytes from shapes (the
work model's ``flash_attention``), in % of its roofline."""


def read(view):
    count = getattr(view.work, "flash_attention", None)
    runs = view.runs_of(r"prefill")
    seconds = view.kernel_seconds(runs, "flash_attention")
    if count is None or not runs or seconds <= 0:
        return None
    sh = view.facts["shapes"]
    least = view.least_s(*count(view.cell.config, sh["batch"],
                                sh["prompt_len"]))
    return 100.0 * least * len(runs) / seconds
