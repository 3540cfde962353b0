"""Columns served over the columns the waves could have carried, from
the front door's own counters over the traced window."""


def read(ctx):
    c = ctx.counters
    if not c.get("waves") or not c.get("panel_k"):
        return None
    return 100.0 * c["cols"] / (c["waves"] * c["panel_k"])
