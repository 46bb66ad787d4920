"""Host wall time per call into a ``score_reduce*`` entry point: padding,
packing, transfer, device time and the wait for the answer."""


def read(ctx):
    n = ctx["span_calls"].get("kernel")
    return None if not n else 1e6 * ctx["span_self_s"]["kernel"] / n
