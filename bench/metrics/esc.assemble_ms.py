import span_stats


def read(run):
    """Host time in ``repro.esc.assemble`` (the host COO->CSR of the ESC
    output and its upload) per product, in ms."""
    return span_stats.per_product(run, "repro.esc.assemble",
                                  lambda s: 1e3 * s.seconds)
