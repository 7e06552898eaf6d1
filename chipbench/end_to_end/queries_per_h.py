"""3600 x queries in the window's whole passes / the seconds those passes
took, from the first one's start to the last one's end."""

from chipbench import window


def read(ctx):
    return window.queries_per_hour(
        len(ctx.passes), len(ctx.traffic["queries"]),
        ctx.passes[0].start_s, ctx.passes[-1].end_s)
