"""layer: engine scheduler (serve/llm_engine.py: a decode block ends
with its last live row).  moves: serve_tpot_mean_ms.  source:
program_counter: ``EngineStats`` over the window: the decode steps the
blocks ran over the steps they were offered (``block_steps_run /
block_steps_offered`` between the window's two snapshots; offered is
``block_size`` a block fetched).  1.0: every block ran to its end, some
row was live at each of its steps.  Below it: blocks ended early, or ran
no step at all (the block dispatched behind a lone request's last one),
and what they left out is steps in which the device would have served
nobody.  It says how often the early end engages, not what it saves: a
block that runs all its steps with one row live of 32 reads 1.0."""

from chipbench.lib import spans


def read(run):
    ran, offered = (spans.stats_delta(run, k) for k in
                    ("block_steps_run", "block_steps_offered"))
    if ran is None or not offered:
        return None
    return ran / offered
