"""Self time of the spans around the loop's ``prepare_batch`` and
``prepare_complete`` hooks, less their nested kernel spans, per instant."""


def read(ctx):
    s = ctx["span_self_s"].get("stage")
    return None if s is None or not ctx["instants"] else 1e6 * s / ctx["instants"]
