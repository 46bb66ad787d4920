"""Self time of the spans around ``ClusterRun.route`` per event."""


def read(ctx):
    s = ctx["span_self_s"].get("route")
    return None if s is None or not ctx["events"] else 1e6 * s / ctx["events"]
