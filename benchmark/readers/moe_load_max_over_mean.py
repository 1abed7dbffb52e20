"""How unevenly the router loads the held experts: ``serve.fetch``'s
``moe_load_max_over_mean`` (a layer's busiest held expert over its mean
one, the worst layer of one generate call), the mean over the calls of
the window.  Read from the process tracer's ring; a program whose
``serve.fetch`` carries no such argument gives nothing to read."""
from benchmark.readers import _program_spans


def read(ctx):
    ring = _program_spans.ring()
    vals = [s.args["moe_load_max_over_mean"] for s in ring or ()
            if s.name == "serve.fetch" and s.args
            and "moe_load_max_over_mean" in s.args]
    return sum(vals) / len(vals) if vals else None
