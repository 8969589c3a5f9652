"""Bytes a client update puts on the uplink: the ledger's knowledge and
weight frames charged in the window, over the window's client updates."""


def read(ctx):
    if not ctx["updates"]:
        return None
    return ctx["uplink_bytes"] / ctx["updates"]
