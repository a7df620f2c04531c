"""setup_s: host seconds from process start to the start of the window:
imports, weights, compiles or compile-cache loads, warm-up and (decode
cells) the prefill of every session's long context."""


def read(run):
    return run.window[0]
