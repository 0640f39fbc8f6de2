"""Mean wait of a request between its submission and the launch of its
prefill (engine admission, prefill lane), over the requests whose prefill
launched in the traced window: the engine's ``admission_wait_s`` over its
``admitted`` counter."""
from bench import program


def read(run):
    c = program.counts(run)
    if not c or not c.get("admitted"):
        return None
    return 1e3 * c.get("admission_wait_s", 0.0) / c["admitted"]
