"""output_tok_s: output tokens committed to requests by the fused steps of
the window, over the window's host seconds."""


def read(run):
    w0, w1 = run.window
    tokens = sum(n for r in run.requests for t, n in r.emissions if w0 < t <= w1)
    return tokens / (w1 - w0)
