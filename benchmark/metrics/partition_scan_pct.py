"""Columns the histograms had to read (a tree's root rows once and the
smaller child's rows at each split) over the columns the kernel's calls
were handed under the leaf-ordered partition (the elected children's padded
spans, or the whole table where the spans would not shrink it), both
counted by the program itself: `hist.cols_needed` over `hist.cols_scanned`
of its registry, with `sampled=0`, the unsampled chunk program. Cannot pass
100. None where the program keeps no such counters."""
from lib import movework


def read(ctx):
    share = movework.ratio("hist.cols_needed", "hist.cols_scanned",
                           sampled=0)
    return None if share is None else 100.0 * share
