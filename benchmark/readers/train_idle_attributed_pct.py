"""Of chip 0's idle seconds in gaps of at least 50 us, the share whose
midpoint lies inside a leaf ``bigdl.*`` span of the DRIVER thread: how
much of the device's waiting the program's own spans explain."""
from benchmark.readers import _program_spans


def read(ctx):
    return _program_spans.idle_attributed_pct(ctx, "driver")
