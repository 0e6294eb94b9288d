def read(run):
    """Share of the window in which no operation ran on the device, in %."""
    if run.trace is None or not run.trace.n_devices:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
