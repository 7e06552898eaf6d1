"""Scan / decode: milliseconds a pass spends reading and decoding Parquet
into Arrow batches (the program's ``scan:load`` spans, the union over
its threads), median over the traced passes. 0 in a resident cell."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "scan:load")
