"""Tokens trained in the window / window seconds / chips.  Host clock at
the loop's own report boundaries (each after the step's loss was fetched);
whole steps only.  End to end, the train cells."""


def read(run):
    t = run.get("train")
    return t and t["tokens"] / t["window_s"] / run["chips"]
