"""layer: engine scheduler (serve/llm_engine.py, a drafting engine's
block).  moves: serve_tpot_mean_ms.  source: program_counter:
``EngineStats`` over the window: drafts that stood over drafts verified
(``drafts_accepted / drafts_proposed`` between the window's two
snapshots; steps whose row was delivered only).  With seeded random
weights it is what two unrelated heads agree on under sampling, some
0.3; a trained module's is 0.6-0.85.  It scales the tokens a step, not
the step's device work."""

from chipbench.lib import spans


def read(run):
    stood, drafts = (spans.stats_delta(run, k) for k in
                     ("drafts_accepted", "drafts_proposed"))
    if stood is None or not drafts:
        return None
    return stood / drafts
