"""Host operators: milliseconds a pass spends in the host hash join,
keys to ids and the build side sorted (``join:build``), matching and taking
the output columns (``join:probe``); each the union over the program's
threads, added; median over the traced passes."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "join:build", "join:probe")
