def read(run):
    """Seconds from process start to the first timed operation."""
    return run.setup_s
