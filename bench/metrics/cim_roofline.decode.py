"""Blocked CiM Pallas MAC in the fused decode program: the least time of
its calls (every layer's projections on all slot rows; operations at the
chip's peak for the configuration's operand types, or the least bytes at
HBM speed, whichever bounds) over the kernels' device time, summed over
the traced decode steps. Moves tokens_per_s."""
import peaks
import work


def read(run):
    t = run.trace
    n_steps = len(t.modules.get("decode", [])) if t else 0
    kernel = t.kernel_s.get("decode", 0.0) if t else 0.0
    if not n_steps or kernel <= 0:
        return None
    c = run.cell.config
    p = peaks.peaks(run.device_kind)
    ops, nbytes = work.cim_step(c, run.n_slots)
    least, _ = work.least_time(ops, nbytes, work.ops_peak(c, p), p)
    return 100.0 * n_steps * least / kernel
