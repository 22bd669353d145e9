"""Device time of one fused decode program (mean over the traced
window's executions). Moves tokens_per_s."""


def read(run):
    t = run.trace
    if t is None or not t.modules.get("decode"):
        return None
    d = t.modules["decode"]
    return 1e3 * sum(d) / len(d)
