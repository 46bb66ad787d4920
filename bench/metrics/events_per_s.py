"""Events completed over the window's wall time (host clock).  An event is
one arrival routed, one launch or one segment completion."""


def read(ctx):
    return ctx["events_per_s"] if ctx["events"] else None
