import program_trace


def read(run):
    """Time inside ``repro.spz.groups`` in which no device was busy, per
    product or per flush, in ms."""
    if run.program is None:
        return None
    return program_trace.metrics(run.program).get("driver.idle_ms")
