"""layer: kernels (ops/paged_attention.py, the absorbed latent decode).
moves: serve_tpot_mean_ms.  source: device_trace + program_counter: the
least time the chip could take for the latent kernel's traced work, over
the kernel's device time.

- work: cached positions read a layer step come from the engine's
  counters over the TRACED interval (``mla_context_tokens /
  mla_layer_steps`` between the snapshots the runner takes at the
  trace's start and stop: rows whose token was delivered only), times
  the layer steps inside the trace (the kernel runs once a layer a
  step: its executions);
- least time: ``lib/mla_bytes.py decode_seconds``: the larger of the
  model's 1,152 B a cached token over HBM bandwidth and its 69.6 kFLOP
  over the bf16 peak (``lib/peaks.py``; at these sizes the bytes),
  whatever the pool's layout pads a row to;
- time: the kernel's device time in the trace (``lib/mla_trace.py``).

A kernel that read a dead row's pages, or pages past a row's context,
could not pass (live context / table span) here; the counter counts
delivered tokens, so it cannot read above 1."""

from chipbench.lib import mla_bytes, mla_trace
from chipbench.lib.peaks import peaks_for


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    delta = lambda k: s1[k] - s0[k] if k in s0 and k in s1 else None  # noqa: E731
    steps, tokens = delta("mla_layer_steps"), delta("mla_context_tokens")
    kernel = mla_trace.of(run).get("kernel") or {}
    if not steps or not tokens or not kernel.get("seconds") \
            or run["device"]["platform"] != "tpu":
        return None
    need = mla_bytes.decode_seconds(
        run["config"], tokens / steps * kernel["runs"],
        peaks_for(run["device"]["kind"]))
    return need / kernel["seconds"]
