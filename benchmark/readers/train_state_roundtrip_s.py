"""Seconds the plan engine spent moving whole state trees through the
host BEFORE the last entry of ``optimize()``: every ``plan.init_state``
and ``plan.sync_to_model`` in the process tracer's ring that ends
before the last ``plan.init_state`` starts — what the re-entries of one
optimizer cost a job's set-up."""
from benchmark.readers import _program_spans


def read(ctx):
    ring = _program_spans.ring()
    if not ring:
        return None
    inits = [s for s in ring if s.name == "plan.init_state"]
    if not inits:
        return None
    last = max(s.start for s in inits)
    return sum(s.duration for s in ring
               if s.name in ("plan.init_state", "plan.sync_to_model")
               and s.end is not None and s.end <= last)
