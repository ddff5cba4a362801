"""device_idle.closed (device layer): the share (%) of the traced window
in which no operation ran on the chip -- 1 minus the union of the
device trace's op intervals over the window, averaged over the chips."""


def read(run):
    if run.reduced is None or run.reduced.n_devices == 0:
        return None
    return run.reduced.idle_share() * 100.0
