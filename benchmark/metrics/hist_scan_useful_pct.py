"""Columns the histograms had to read (a tree's root rows once and the
smaller child's rows at each split) over the columns the kernel's calls were
handed, both counted by the program itself: `hist.cols_needed` over
`hist.cols_scanned` of its registry, with `sampled=1`, the one chunk program
the window runs (the warm rounds past GOSS's start and the probe ran it too:
the same population). Cannot pass 100: a call scans at least the rows of
the leaves it builds. None where the program keeps no such counters."""


def read(ctx):
    try:
        from lightgbm_tpu import obs
        reg = obs.registry()
        needed = reg.get("hist.cols_needed", sampled=1)
        scanned = reg.get("hist.cols_scanned", sampled=1)
    except (ImportError, AttributeError):
        return None
    if needed is None or scanned is None or not scanned.value:
        return None
    return 100.0 * needed.value / scanned.value
