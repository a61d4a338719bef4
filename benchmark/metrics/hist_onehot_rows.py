"""One-hot rows the histogram kernel built for each column of rows it was
handed, both counted by the program itself: `hist.onehot_elems` over
`hist.cols_scanned` of its registry, with `sampled=1`, the one chunk program
the window runs. The kernel's VPU compares and its MXU rows are both
proportional to it: every column at 256 bins reads features x 256 (padded
to whole feature blocks); a kernel that builds rows only for the bins a
column has reads about the sum of the columns' bin counts. A count, not a
speed. None where the program keeps no such counters."""


def read(ctx):
    try:
        from lightgbm_tpu import obs
        reg = obs.registry()
        elems = reg.get("hist.onehot_elems", sampled=1)
        scanned = reg.get("hist.cols_scanned", sampled=1)
    except (ImportError, AttributeError):
        return None
    if elems is None or scanned is None or not scanned.value:
        return None
    return elems.value / scanned.value
