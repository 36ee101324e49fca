"""``traceback_ms`` at the tail bucket: time per decision in the root read,
the detour lists and the release of the tables, in ms (the program's
``ltsp.fetch_root``, ``ltsp.traceback`` and ``ltsp.release`` spans,
trace)."""

from harness import load_reader

read = load_reader("traceback_ms")
