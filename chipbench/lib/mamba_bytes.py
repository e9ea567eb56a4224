"""Bytes a Mamba-2 layer REQUIRES in a decode step, from the published
sizes under the ``granitemoehybrid`` key names: ``lib/ssm_bytes.py``'s
count (a live row's state read and written in float32, its convolution
tail in bfloat16, its vectors), handed this family's keys under the
names it reads.  At granite-4.0-h-micro's sizes: state 2 x 2,097,152
B, tail 2 x 26,112 B, vectors 50,176 B: 4,296,704 B a live row a layer
step, of which the state is 97.6%."""

from chipbench.lib import ssm_bytes

_AS = {"mamba_num_heads": "mamba_n_heads", "mamba_head_dim": "mamba_d_head",
       "ssm_state_size": "mamba_d_state", "n_groups": "mamba_n_groups",
       "conv_kernel": "mamba_d_conv"}


def _mapped(cfg: dict) -> dict:
    return {theirs: cfg[ours] for theirs, ours in _AS.items()}


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state in one layer (float32)."""
    return ssm_bytes.state_bytes(_mapped(cfg))


def decode_row_bytes(cfg: dict) -> int:
    """What one live row's decode step moves in one layer."""
    return ssm_bytes.decode_row_bytes(_mapped(cfg))
