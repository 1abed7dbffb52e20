"""Host seconds the program's initialisers spent drawing weights
(``nn/initialization.py`` ``_uniform`` / ``_normal``, on the host or on
the device): the sum of the counter family
``bigdl_init_draw_seconds_total`` of the default registry after the
run.  The model's constructor draws every weight before the benchmark's
seeded ones replace them, so this is a part of ``setup_s`` only the
program can shorten.

What the seconds hold depends on where the draw runs.  On the host
(``where="host"``: the GPT-2 and Mistral cells) they are the draw
itself and read the same with or without a compile cache.  Under
``device_draw`` (``where="device"``: the Command A+ and GLM cells) they
are the host's time to build and enqueue one small program a shape —
the draw runs behind it — so they hold that program's COMPILE where
nothing is cached and its cache load where it is: tens of seconds cold,
about a second warm (PERF.md section 5).  Compare a reading only with
one of the same cache state, as ``setup_s`` itself.

None for a program that has no such counter."""

def read(ctx):
    try:
        from bigdl_tpu.telemetry.registry import default_registry
    except ImportError:
        return None
    family = default_registry().get("bigdl_init_draw_seconds_total")
    if family is None:
        return None
    return sum(child.value for _, child in family.series())
