"""layer: jitted step (serve/llm_engine.py: the sampler at the end of a
decode step draws only where a live row has a temperature).  moves:
serve_tpot_mean_ms.  source: program_counter: ``EngineStats`` over the
window: the decode steps whose sampler took its drawing branch over the
steps the blocks ran (``block_steps_drawn / block_steps_run`` between
the window's two snapshots; the device counts both, in the block's
loop).  0.0: every step served greedy rows alone, and computed an argmax
and no noise.  1.0: every step had a sampled row live and paid the draw
for every row (``rows x vocabulary`` threefry hashes), as every step did
before the conditional.  It says how often the draw runs, not what it
costs: that is ``rows x vocabulary`` of the cell."""

from chipbench.lib import spans


def read(run):
    drawn, ran = (spans.stats_delta(run, k) for k in
                  ("block_steps_drawn", "block_steps_run"))
    if drawn is None or not ran:
        return None
    return drawn / ran
