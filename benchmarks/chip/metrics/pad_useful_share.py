"""Real over padded DP cells of the window's launches, in %: the program's
exact counts (``repro.obs.KernelProfile``)."""


def read(run):
    padded = sum(r.padded_cells for r in run.launches)
    if not padded:
        return None
    return 100.0 * sum(r.real_cells for r in run.launches) / padded
