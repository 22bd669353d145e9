"""Share of the window's wall time spent in ``step()`` calls that ran a
fill (the engine re-prefills every slot row and merges the fresh cache;
the benchmark's spans around each call). Moves tokens_per_s."""


def read(run):
    w = run.window
    fill = sum(s.t1 - s.t0 for s in w.steps if s.filled)
    return 100.0 * fill / (w.t1 - w.t0) if w.steps else None
