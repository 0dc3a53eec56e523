"""cc_ms: device time of the jitted ``connected_components`` program per build.

Summed over the ``XLA Modules`` events named ``jit_connected_components`` in the
profiler trace of the traced builds, divided by the builds traced. A
trace with modules but none of that name is reported on standard error
(``bench.tracing.program_ms``): the program was renamed or fused away.
"""
from bench import tracing


def read(run):
    return tracing.program_ms(run, "connected_components")
