"""layer: jitted step (train/sharded/executor.py ``train_grad``).
moves: train_tokens_per_s_per_chip.  source: device_trace: device time
of the forward-and-backward program (``XLA Modules`` line) per traced
step."""

from chipbench.lib import spans


def read(run, program=r"^train_grad$"):
    found, traced = spans.module_seconds(run, program), run.get("traced")
    if not found or not traced or not traced.get("steps"):
        return None
    return 1e3 * found / traced["steps"]
