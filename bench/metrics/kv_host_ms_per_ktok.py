"""Host time of a prompt's KV round trip per 1,000 prompt tokens (page
pool): the prefill's KV to host (``paged.prefill.kv_to_host``) and its
write back into the pool (``paged.kv.append``: padding, reshaping, the
copy to the device and the scatter's launch).

A prefill counts when its ``prefill_tokens`` counter event falls inside the
traced window; its spans are those that follow that event on the prefill
lane's thread, up to the lane's next ``paged.prefill.step``."""
from bench import program

KV = ("paged.prefill.kv_to_host", "paged.kv.append")


def read(run):
    evs = program.events(run)
    if not evs:
        return None
    ns, tokens, counting = 0.0, 0, {}
    for e in evs:
        if e.name == "paged.prefill.step":
            counting[e.line] = False
        elif e.name == program.COUNTER and e.args.get("counter") == "prefill_tokens":
            counting[e.line] = program.in_window(run, e)
            tokens += e.args.get("n", 0) if counting[e.line] else 0
        elif e.name in KV and counting.get(e.line):
            ns += e.end - e.start
    if not tokens or ns <= 0:
        return None
    return ns * 1e-6 / (tokens / 1e3)
