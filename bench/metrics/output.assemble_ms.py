import program_trace


def read(run):
    """Host time in ``repro.spz.assemble`` per product or per flush, in
    ms."""
    if run.program is None:
        return None
    return program_trace.metrics(run.program).get("output.assemble_ms")
