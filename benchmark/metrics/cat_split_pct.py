"""Share of the traced trees' splits that are set-splits (decision-type
bit 0 in the model text), counted from the window's own trees as
lib/work.py counts work. None where no tree was traced."""


def read(ctx):
    trees = ctx.get("trees_traced") or []
    splits = sum(len(t["decision_type"]) for t in trees)
    if not splits:
        return None
    cat = sum(int((t["decision_type"] & 1).sum()) for t in trees)
    return 100.0 * cat / splits
