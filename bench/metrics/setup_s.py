"""Process start to the first timed request, compiles included, in s."""


def read(ctx):
    return ctx["setup_s"]
