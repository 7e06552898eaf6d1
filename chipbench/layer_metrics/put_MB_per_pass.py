"""Upload: megabytes of encoded planes (data + validity, as the HBM cache
counts them) a pass put on the device, cached or streamed through, from the
``bytes`` of the program's ``device:put`` spans; median over the traced
passes. ``upload_MB_per_pass`` beside it sees only what the cache kept."""

from chipbench import program_spans


def read(ctx):
    phases = program_spans.per_pass(ctx)
    if phases is None:
        return None
    return phases.get("device:put", {}).get("bytes", 0) / 1e6
