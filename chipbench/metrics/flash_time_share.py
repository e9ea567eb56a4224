"""layer: kernels (ops/flash_attention.py).  moves:
train_tokens_per_s_per_chip.  source: device_trace: self time of the
flash kernels' device operations over the device's busy time.

The program gives its kernels no name of their own (listed in PERF.md for
the tracing issue), so the trace shows them under the name of the module
method that calls them: the forward and the two backward Pallas calls all
sit in ``Attention._train_attend``."""

from chipbench.lib.trace import share_of_busy

PATTERN = r"_train_attend|_kernel_attend_sharded|flash"


def read(run):
    return share_of_busy(run.get("trace") or {}, PATTERN)
