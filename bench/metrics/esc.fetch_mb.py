import span_stats


def read(run):
    """Bytes ``repro.esc.fetch`` copies to the host (its ``bytes`` stat)
    per product, in MB (1e6 bytes)."""
    return span_stats.per_product(run, "repro.esc.fetch",
                                  lambda s: 1e-6 * int(s.stats["bytes"]))
