def read(run):
    """Requests completed over the window's elapsed time."""
    return len(run.done) / run.window_s if run.done else None
