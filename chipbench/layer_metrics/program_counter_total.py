"""A counter family of the program's default registry at the end of the
run, summed over its children of one ``stage`` whose ``program`` is a
compiled block of the program's (not ``other``: the benchmark's own
jits, its reference and its weight draw, stay out of the sum).

For ``paddle_compile_seconds_total`` that is the wall time the whole
process spent in one compile stage (``trace``: the Python of the
lowering rules; ``lower``: jaxpr to MLIR; ``backend_compile``: XLA, or
the persistent cache's load when it hits). With no compile inside the
window (``compiles_in_window.*``) all of it is set-up.

A family that exists reads 0.0 when the stage saw no event; None only
where the program has no such family (a parent without it)."""

OTHER = "other"


def read(obs, family, stage):
    from paddle_tpu.observability import metrics
    fam = metrics.default_registry().get(family)
    if fam is None:
        return None
    i_stage = fam.labelnames.index("stage")
    i_program = fam.labelnames.index("program")
    return sum(child.value for labels, child in fam.children().items()
               if labels[i_stage] == stage and labels[i_program] != OTHER)
