def read(run):
    """Mean host wall time of the service's flushes in the window
    (``FlushRecord.wall_s``), in s."""
    walls = [f.wall_s for f in run.flushes]
    return sum(walls) / len(walls) if walls else None
