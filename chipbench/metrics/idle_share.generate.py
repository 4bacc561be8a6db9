"""idle_share.generate: the share of the traced window in which no
operation ran on the device, in %."""


def read(view):
    share = view.idle_share()
    return None if share is None else 100.0 * share
