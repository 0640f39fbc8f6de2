"""Model FLOP/s utilisation of the whole serving step (whole step against
the chip's peak): the model FLOPs of every prompt token prefilled and every
row decoded in the traced window, over window x chips x peak FLOP/s."""
from bench import xplane as tr


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    m, w = run.cfg["model"], run.itemsize
    flops = 0.0
    for i, _, _ in tr.calls(run.trace, run.cfg["modules"], "prefill"):
        if i < len(run.prefills) and run.prefills[i]:
            flops += run.work.prefill(m, run.prefills[i], w)[0]
    for i, _, _ in tr.calls(run.trace, run.cfg["modules"], "decode"):
        if i < len(run.decodes):
            flops += run.work.decode(m, run.decodes[i][2], w)[0]
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.workload["chips"] * run.peak["flops_per_s"])
