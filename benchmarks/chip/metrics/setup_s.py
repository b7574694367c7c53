"""Set-up: from process start to the first timed request, with loading,
compiling (or reading the compile cache) and warming up."""


def read(ctx):
    return ctx.setup_s
