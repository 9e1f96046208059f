"""Share (%) of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(run):
    if not run.busy_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace_window_s)
