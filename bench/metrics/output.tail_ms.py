def read(run):
    """Mean time from a product's last device operation to the caller
    holding its CSR (the end of its ``bench.product`` span), in ms."""
    if run.trace is None or not run.trace.last_device_end:
        return None
    spans = run.trace.spans
    tails = [spans[i][2] - end for i, end in run.trace.last_device_end.items()
             if spans[i][0] == "bench.product"]
    return 1e-6 * sum(tails) / len(tails) if tails else None
