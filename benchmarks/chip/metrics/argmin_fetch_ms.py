"""Time per decision copying the argmin plane ``C`` from the device to the
host, in ms (the program's ``ltsp.fetch_argmin`` spans, trace)."""

from program_spans import per_decision_ms


def read(run):
    return per_decision_ms(run, ("ltsp.fetch_argmin",))
