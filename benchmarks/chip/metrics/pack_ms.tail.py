"""``pack_ms`` at the tail bucket: host time per decision before the launch,
in ms (the program's ``ltsp.rescale``, ``ltsp.guard`` and ``ltsp.pack``
spans, trace)."""

from harness import load_reader

read = load_reader("pack_ms")
