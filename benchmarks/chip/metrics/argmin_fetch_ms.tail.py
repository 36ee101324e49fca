"""``argmin_fetch_ms`` at the tail bucket: time per decision in the device
walk of the argmin plane and the copy of its outputs, in ms (the program's
``ltsp.fetch_argmin`` spans, trace)."""

from harness import load_reader

read = load_reader("argmin_fetch_ms")
