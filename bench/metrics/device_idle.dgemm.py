"""Share of the window in which no operation ran on the device, in %."""


def read(trace, counts):
    return 100.0 * trace.idle_share if trace.busy_s > 0 else None
