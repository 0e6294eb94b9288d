import program_trace


def read(run):
    """Device time under the ``spz.expand`` scope per product or per
    flush, summed over the devices, in ms."""
    if run.program is None:
        return None
    return program_trace.metrics(run.program).get("device.expand_ms")
