"""accept_len: tokens committed per active row per fused step
(n_accepted + 1), mean over the steps of the window."""


def read(run):
    rows = [k for s in run.window_steps() for k in s.committed]
    return sum(rows) / len(rows) if rows else None
