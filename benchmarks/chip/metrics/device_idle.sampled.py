"""Share of the traced window in which no operation ran on the device
(1 - the union of device op intervals over the window)."""


def read(run):
    from devtrace import busy_s
    td = run.trace_data
    if td is None or not td.device_ops:
        return None
    return 100.0 * (1.0 - busy_s(td) / td.window_s)
