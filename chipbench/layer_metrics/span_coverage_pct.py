"""The instrument itself: of the traced queries' wall, the share that
lies inside some leaf span of the program (the union of them, so that
parallel threads count once). What is left is time no span names yet."""

from chipbench import program_spans


def read(ctx):
    t = program_spans.totals(ctx)
    if t is None or not t["wall_us"]:
        return None
    return 100.0 * t["covered_us"] / t["wall_us"]
