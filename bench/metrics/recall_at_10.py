"""Mean share of the exact full-dimension top-10 (the benchmark's own
reference, HIGHEST precision) found in each answer, over every request due
in the window; a failed request found none."""


def read(ctx):
    return ctx.recall
