"""layer: jitted step.  moves: train_tokens_per_s_per_chip.
source: program_counter (tokens from the loop's step count) over the host
clock, times the benchmark's own operations per token
(``lib/flops.py``: recompute not counted, causal attention once, the
embedding gather not counted) over chips times the peak in
``lib/peaks.py``.  An end-to-end utilisation, not a kernel's roofline."""

from chipbench.lib.flops import train_flops_per_token
from chipbench.lib.peaks import peaks_for


def read(run):
    t = run.get("train")
    if not t or run["device"]["platform"] != "tpu":
        return None
    per_token = train_flops_per_token(run["config"], t["seq_len"])
    peak = peaks_for(run["device"]["kind"])["bf16_flops"]
    return t["tokens"] / t["window_s"] * per_token / (run["chips"] * peak)
