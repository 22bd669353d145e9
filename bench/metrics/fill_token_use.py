"""Share of the fill tokens computed in the window that were prompt
tokens of the requests the fills admitted (serve/engine
ContinuousBatcher: a fill computes every one of its ``n_slots`` rows at
the padded bucket width). Read from the window's record of each fill
(the rows it admitted, in queue order, and its padded width, both
checked against the engine's slot table after every step); it equals
the engine's own counters, ``fill_tokens_prompt`` over
``fill_tokens_computed`` in ``stats()``, taken across the window.
Moves tokens_per_s."""


def read(run):
    w = run.window
    fills = [s for s in w.steps if s.filled]
    computed = sum(run.n_slots * s.s_pad for s in fills)
    if not computed:
        return None
    admitted = sum(s.filled for s in fills)
    prompt = sum(len(t.req.prompt) for t in w.due[:admitted])
    return 100.0 * prompt / computed
