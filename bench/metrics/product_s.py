def read(run):
    """The window's elapsed time over the products completed."""
    return run.window_s / len(run.done) if run.done else None
