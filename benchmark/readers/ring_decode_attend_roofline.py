"""The attends of a decode step over rings and a growing cache: the K
and V they must read (``counts_smallthinker.decode_attend_bytes`` for
the mean dispatched bucket at the step's mean context: a window layer's
whole ring once the context has passed the window, the global layer's
written part) at the chip's memory bandwidth, over the traced self time
a step of the operations under ``attention.decode_attend`` inside
``generate.decode_step``.  A program that reads a cache twice, or the
unwritten tail of one, reads more than the count: the extra is its
loss.  A reading over 100 % is a wrong count."""
from benchmark import counts_smallthinker
from benchmark.readers import _st_scopes


def read(ctx):
    sh = _st_scopes.shapes(ctx)
    if sh is None:
        return None
    _, rows, context = sh
    seconds = _st_scopes.scope_step_seconds(ctx, "attention.decode_attend/")
    if not seconds:
        return None
    nbytes = counts_smallthinker.decode_attend_bytes(ctx.config, rows,
                                                     context)
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds
