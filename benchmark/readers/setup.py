"""Process start to the first timed step, in seconds."""


def read(ctx):
    return ctx["setup_s"]
