def read(run):
    """Mean host time of ``plan()`` per product, in ms."""
    t = [op.plan_s for op in run.done if op.plan_s is not None]
    return 1e3 * sum(t) / len(t) if t else None
