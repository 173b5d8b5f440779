"""Share of the traced slice in which no operation ran on the device
(one minus the union of device-op intervals), averaged over chips."""


def read(run):
    if run.trace_summary is None:
        return None
    s = run.trace_summary
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
