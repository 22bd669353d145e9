"""Mean number of slots decoded per fused decode step over the window
(serve/engine ContinuousBatcher, counted from the engine's slot table
after every ``step()``). Moves tokens_per_s."""


def read(run):
    rows = [s.decoded for s in run.window.steps if s.decoded]
    return sum(rows) / len(rows) if rows else None
