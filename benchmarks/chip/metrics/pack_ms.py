"""Host time per decision before the launch, in ms: rescale, guard and
packing with its host-to-device puts (the program's ``ltsp.rescale``,
``ltsp.guard`` and ``ltsp.pack`` spans, trace)."""

from program_spans import per_decision_ms


def read(run):
    return per_decision_ms(run, ("ltsp.rescale", "ltsp.guard", "ltsp.pack"))
