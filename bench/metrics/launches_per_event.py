"""Calls into the three ``score_reduce*`` entry points per event."""


def read(ctx):
    if not ctx["events"]:
        return None
    return sum(ctx["launches"].values()) / ctx["events"]
