"""Host time a round spent on the validation set: the program's own spans
`train/valid_update` (the dispatch of the round's trees over the valid
rows) and `train/eval` (the fetch of the valid scores and the metric), on
the host's clock, over the traced rounds. The entry turns the program's
metrics on for those rounds and hands their histograms' sums. None where
the entry handed nothing."""


def read(ctx):
    spans = ctx.get("program_spans")
    if not spans or not ctx.get("iters_traced"):
        return None
    if "train/eval" not in spans:
        return None
    s = spans["train/eval"] + spans.get("train/valid_update", 0.0)
    return s * 1e3 / ctx["iters_traced"]
