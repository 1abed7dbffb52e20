"""How far from doubly stochastic the worst residual map of the window
was: ``serve.fetch``'s ``mhc_sinkhorn_err`` (the largest ``|rowsum - 1|``
or ``|colsum - 1|`` of a hyper-connection's ``H_res`` that one generate
call computed, prefill and every step, every sublayer — carried through
the cache and fetched with the tokens), the largest over the calls of
the window.  Read from the process tracer's ring; a program whose
``serve.fetch`` carries no such argument gives nothing to read."""
from benchmark.readers import _program_spans


def read(ctx):
    ring = _program_spans.ring()
    vals = [s.args["mhc_sinkhorn_err"] for s in ring or ()
            if s.name == "serve.fetch" and s.args
            and "mhc_sinkhorn_err" in s.args]
    return max(vals) if vals else None
