"""Host time per decode step of the resident state's round trip (decode
lane): stacking each row's state for the step (``paged.decode.state_in``)
and bringing the new state back to the host, split per row
(``paged.decode.state_out``), over the engine's ``decode_steps`` in the
traced window."""
from bench import program


def read(run):
    ms = program.span_ms(run, ("paged.decode.state_in", "paged.decode.state_out"))
    c = program.counts(run)
    if ms is None or not c or not c.get("decode_steps"):
        return None
    return ms / c["decode_steps"]
