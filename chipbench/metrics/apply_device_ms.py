"""layer: jitted step (train/sharded/executor.py ``train_apply``).
moves: train_tokens_per_s_per_chip.  source: device_trace: device time
of the optimizer program (``XLA Modules`` line) per traced step."""

from chipbench.metrics import grad_device_ms


def read(run):
    return grad_device_ms.read(run, program=r"^train_apply$")
