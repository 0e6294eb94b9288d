def read(run):
    """Device program executions per product, counted in the trace."""
    if run.trace is None or not run.trace.modules or not run.done:
        return None
    return sum(n for n, _ in run.trace.modules.values()) / len(run.done)
