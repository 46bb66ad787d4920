"""Process start to window start: JAX and TPU start-up, fleet build,
compiles or compile-cache loads, warm-up replay (host clock)."""


def read(ctx):
    return ctx["setup_s"]
