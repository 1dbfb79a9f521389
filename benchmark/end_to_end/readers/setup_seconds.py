"""Process start to the window's first due request."""


def read(ctx):
    return ctx.setup_s
