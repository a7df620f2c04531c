"""setup_compile_s: seconds of tracing, lowering, backend compile and
persistent compile-cache loads in the program's compile records
(``repro.obs``) that end before the window starts."""
from bench import program_trace


def read(run):
    return program_trace.compile_seconds_before(run, run.window[0])
