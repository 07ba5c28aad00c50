"""expert_full_buffer_share: share of the expert layers run that took the
static buffer of T·min(k, held) rows because the copies routed to the
experts held here did not fit the compact buffer, in %: 100 x
``moe_full_buffer_layers`` / ``moe_layer_steps``, from the program's
process-wide counters (``repro.api.spans.counters()``).  Totals over every
step the process ran, set-up and window alike.  Nothing to read where the
program keeps no such counters."""


def read(ctx):
    try:
        from repro.api.spans import counters
    except ImportError:
        return None
    c = counters()
    layers = c.get("moe_layer_steps", 0)
    if layers <= 0:
        return None
    return 100.0 * c.get("moe_full_buffer_layers", 0) / layers
