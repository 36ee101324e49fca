"""``idle_unattributed_share`` at the tail bucket: share of the window's
device-idle time in which the host was in none of the program's ``ltsp.*``
spans and no ``verify_schedule`` span, in % (device trace)."""

from harness import load_reader

read = load_reader("idle_unattributed_share")
