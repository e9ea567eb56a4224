"""layer: jitted step.  moves: train_tokens_per_s_per_chip.
source: device_trace: the device's busy time (union of the ``XLA Ops``
intervals, averaged over the chips) in the traced steps, per step."""


def read(run):
    red, traced = run.get("trace") or {}, run.get("traced")
    if not traced or not red.get("busy_s"):
        return None
    return 1e3 * red["busy_s"] / traced["steps"]
