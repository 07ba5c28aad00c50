"""feed_ms: mean host milliseconds per step inside the storage feed
(``FleetBatcher.next_device_batch``), timed by the harness's wrapper over
the window's steps."""


def read(ctx):
    feed = ctx.get("feed_s") or []
    if not feed:
        return None
    return 1000.0 * sum(feed) / len(feed)
