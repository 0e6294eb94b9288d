import span_stats


def read(run):
    """Host time in ``repro.esc.fetch`` (the ESC program's outputs copied
    to the host) per product, in ms."""
    return span_stats.per_product(run, "repro.esc.fetch",
                                  lambda s: 1e3 * s.seconds)
