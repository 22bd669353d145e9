"""Model operations of the tokens decoded in the traced window over the
window's length times the chip's peak for the configuration's operand
types (bfloat16 where activations are bfloat16, int8 where both
operands are ternary). Operations per token are 2 x the parameters of
the dense projections and the unembedding (attention and SSM state
arithmetic left out: a lower bound). Moves tokens_per_s."""
import peaks
import work


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    t0 = run.window.trace_t0
    tokens = sum(s.decoded for s in run.window.steps if t0 is not None and s.t0 >= t0)
    if not tokens:
        return None
    c = run.cell.config
    ops = tokens * work.ops_per_token(c)
    return 100.0 * ops / (t.window_s * work.ops_peak(c, peaks.peaks(run.device_kind)))
