"""layer: kernels (ops/paged_attention.py at two queries a row).  moves:
serve_tpot_mean_ms.  source: device_trace + program_counter: the least
time the chip could take to move what the trace's verify steps had to
move through the paged kernel, over the time ``paged_attention_decode``
took.

- bytes: ``lib/spec_bytes.py verify_bytes``: the pages the live rows'
  two queries read and the two rows each wrote, a pool layer step, from
  the engine's counters over the TRACED interval (``decode_pages_read``,
  ``decode_rows_written`` over ``pool_layer_steps``, between the
  snapshots the runner takes at the trace's start and stop: steps whose
  row was delivered only), times the pool layer steps inside the trace
  (the kernel runs once a pool layer a step: its executions);
- time: the kernel's device time in the trace;
- bound: HBM bandwidth (``lib/peaks.py``); the two products of a page
  (``spec_bytes.verify_flops``) are a fifteenth of that time.

The counters count delivered steps, and a kernel that read a dead row's
pages or pages behind the window would take longer for the same bytes:
neither can read above 1."""

from chipbench.lib import spec_bytes, spec_trace
from chipbench.lib.peaks import peaks_for


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    delta = lambda k: s1[k] - s0[k] if k in s0 and k in s1 else None  # noqa: E731
    steps, pages, rows = (delta(k) for k in (
        "pool_layer_steps", "decode_pages_read", "decode_rows_written"))
    kernel = spec_trace.of(run).get("kernel") or {}
    if not steps or not pages or not rows or not kernel.get("seconds") \
            or run["device"]["platform"] != "tpu":
        return None
    page_size = run["mix"]["server"]["page_size"]
    need = spec_bytes.verify_bytes(
        run["config"], pages / steps * kernel["runs"],
        rows / steps * kernel["runs"], page_size)
    return (need / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
            / kernel["seconds"])
