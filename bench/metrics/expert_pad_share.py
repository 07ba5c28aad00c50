"""expert_pad_share: share of the rows the expert layer's grouped matmul
computes that carry no routed token copy, in %: 100 x (rows computed - rows
routed) / rows computed, from the program's process-wide counters
(``repro.api.spans.counters()``: ``moe_rows_routed``, the copies routed to
the experts held here, and ``moe_rows_computed``, the rows the grouped
matmul's row tiles cover).  Totals over every step the process ran, set-up
and window alike.  Nothing to read where the program keeps no such
counters."""


def read(ctx):
    try:
        from repro.api.spans import counters
    except ImportError:
        return None
    c = counters()
    computed = c.get("moe_rows_computed", 0)
    if computed <= 0:
        return None
    return 100.0 * (computed - c.get("moe_rows_routed", 0)) / computed
