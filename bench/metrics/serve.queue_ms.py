import program_trace


def read(run):
    """Mean time from a request's ``repro.serve.submit`` to the start of
    the flush that answers it, in ms."""
    if run.program is None:
        return None
    return program_trace.metrics(run.program).get("serve.queue_ms")
