"""layer: kernels (ops/paged_attention.py: the decode kernel's row
write).  moves: serve_tpot_mean_ms.  source: program_counter:
``EngineStats`` over the window: rows a pool layer step writes
(``decode_rows_written / pool_layer_steps`` between the window's two
snapshots; rows whose token was delivered, which are the rows the kernel
keeps: each puts the step's token into its own tail page, a row that
holds no request writes nothing).  The XLA scatter it replaced wrote the
whole batch (33 or 65 rows) a layer step.  Like ``decode_pages_read`` it
says what the kernel's bounds name; that the kernel keeps to them is the
tests'."""

from chipbench.lib import spans


def read(run):
    rows, steps = (spans.stats_delta(run, k) for k in
                   ("decode_rows_written", "pool_layer_steps"))
    if rows is None or not steps:
        return None
    return rows / steps
