"""Process start to the first measured request: loading, filling, warming and,
in a run that compiles, compilation."""


def read(ctx, params):
    return ctx["setup_s"]
