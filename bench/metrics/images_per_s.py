"""Images delivered in the window over the window's length (MLPerf
Offline's metric): the window runs from the first call's start to the last
call's return."""


def read(ctx):
    return ctx.delivered / ctx.record.elapsed
