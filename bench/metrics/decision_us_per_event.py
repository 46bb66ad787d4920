"""Self time of the spans around ``EcoSched.on_event`` and
``EcoSched.propose_resizes``, less their nested kernel spans, per event."""


def read(ctx):
    s = ctx["span_self_s"].get("decide")
    return None if s is None or not ctx["events"] else 1e6 * s / ctx["events"]
