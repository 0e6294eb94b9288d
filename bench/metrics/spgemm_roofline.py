import work


def read(run):
    """The least time a product could take on the chip (``work.py``:
    its least bytes at the HBM peak, or its operations at the compute
    peak, whichever is longer) over its device time, in %."""
    if (run.trace is None or not run.trace.modules or not run.done
            or run.least_bytes is None or run.peak is None):
        return None
    device_s = sum(s for _, s in run.trace.modules.values()) / len(run.done)
    least, _ = work.least_seconds(run.least_bytes, run.least_flops, run.peak)
    return 100.0 * least / device_s
