"""Process start to the first timed pass: data for the seed (if absent),
imports, the device check, warm-up passes until one compiles nothing. The
reference answers are computed after the window and are not in here."""


def read(ctx):
    return ctx.setup_s
