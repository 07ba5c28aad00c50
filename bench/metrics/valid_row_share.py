"""valid_row_share: rows that carry data over the rows the compiled step
computes, in % (the tuned schedule's exact counts; Algorithm 1's masks
make up the rest)."""


def read(ctx):
    if not ctx.get("global_rows"):
        return None
    return 100.0 * ctx["valid_rows"] / ctx["global_rows"]
