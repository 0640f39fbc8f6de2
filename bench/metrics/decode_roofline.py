"""Share of the chip's roofline that the decode step reaches (kernels: the
XLA ops of the decode step).

For each traced decode execution, the least time the chip could take is the
larger of the FLOPs and the bytes the algorithm needs for that call's real
rows at their own lengths (``bench/work/<family>.py``) over the peak FLOP/s
and HBM bytes/s; the share is the sum of those over the sum of the
executions' device time."""
from bench import xplane as tr


def read(run):
    if run.trace is None:
        return None
    least, spent = 0.0, 0.0
    for i, a, b in tr.calls(run.trace, run.cfg["modules"], "decode"):
        if i >= len(run.decodes):
            continue
        flops, nbytes = run.work.decode(run.cfg["model"], run.decodes[i][2], run.itemsize)
        least += max(flops / run.peak["flops_per_s"], nbytes / run.peak["hbm_bytes_per_s"])
        spent += (b - a) * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
