"""Device: the share of the traced slice in which no operation ran on the
chip, in percent."""


def read(run):
    tr = run.trace
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
