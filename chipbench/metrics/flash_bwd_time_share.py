"""layer: kernels (ops/flash_attention.py).  moves:
train_tokens_per_s_per_chip.  source: device_trace: self time of the two
backward flash kernels, found by the names the program gives them
(``flash_bwd_dq``, ``flash_bwd_dkv``), over the device's busy time."""

from chipbench.lib.trace import share_of_busy

PATTERN = r"flash_bwd_dq|flash_bwd_dkv"


def read(run):
    return share_of_busy(run.get("trace") or {}, PATTERN)
