"""Share of the window in which no operation ran on the device: one
minus the union of device operation intervals over the window."""


def read(run):
    return 100.0 * run.trace.idle_share if run.trace else None
