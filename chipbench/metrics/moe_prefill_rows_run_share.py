"""layer: kernels (ops/moe.py, the grouped formulation of the dropless
expert layer in a prefill program).  moves: serve_tpot_mean_ms.  source:
program_counter: ``EngineStats`` over the window, in an engine whose
model holds a SHARE of its experts: the pair rows its prefill programs'
grouped expert products ran over (slabs run x slab, every expert layer)
over the (token, choice) pairs they were given (``moe_prefill_pairs_run
/ moe_prefill_pairs`` between the window's two snapshots; each program
counts its own on the device).  1.0: every pair's row went through the
sort, the gather and the three products, held here or not, as before the
slabs.  The floor is the share of the experts held (1/8 in serve-docqa
and serve-think, 1/4 in serve-workers, 1/16 in serve-longgen); what
stands above it is the slab's headroom and whole tiles.  It says how far
the mechanism engages, not what a row costs."""

from chipbench.lib import spans


def read(run):
    ran, given = (spans.stats_delta(run, k) for k in
                  ("moe_prefill_pairs_run", "moe_prefill_pairs"))
    if ran is None or not given:
        return None
    return ran / given
