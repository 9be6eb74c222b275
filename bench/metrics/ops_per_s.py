"""ops_per_s: operations whose answers came back inside the window,
over the window's seconds (host clock)."""


def read(run):
    return run.n_retired / run.window_s
