"""Continuous-batching LLM inference engine (the TPU serving core).

The reference serves LLMs by scaling replicas and batching whole requests
(`python/ray/serve/batching.py`); its Serve LLM benchmark surface is
llama-3-8b qps/p50/p99 (BASELINE.md north-star row).  On TPU the win is
*iteration-level* scheduling (Orca-style): one jitted decode step over a
fixed slot grid, with requests installed into free decode slots and
evicted the step they finish — no compile-shape churn, no head-of-line
blocking behind a long generation.

Design (shaped by one hard constraint: a device->host fetch is a host
synchronization point — it drains the dispatch queue and costs a fixed
host-dispatch latency that a single decode step does not amortize — so
the engine does exactly ONE fetch per decode block and one per
iteration's prefills):

  - The pool.  K/V lives in a shared page pool addressed through
    per-row block tables (ops/paged_attention.py): ONE stacked cache
    leaf ``kv_pages`` [layers, pool_pages, kv_heads, page_size, row],
    declared by the model (models/gpt.py GPT) and chained, donated,
    through every engine program.  ``row`` is what the model's
    attention caches of a token: ``2*head_dim`` (K|V), or ONE latent
    row for all heads (``cfg.cache_row_width``, ``kv_heads`` 1); the
    engine reads the shape off the leaf and is otherwise indifferent.  It rides the model's layer
    scan and the block's step scan as loop-carried state; a layer
    writes its rows at ``[layer, page, :, offset]`` and the kernel reads
    ``[layer, page]`` (``write_kv_pages`` for a prompt, the decode
    kernel itself for a step's row / the DMA source).  Nobody
    slices a layer out of it: a decode step or a prefill wave moves the
    rows it writes and the pages it reads, whatever ``kv_pool_pages``
    is (tests/test_chip_compile.py holds that in the compiled
    programs).  Decode attention reads only the pages a row occupies
    (the Pallas kernel's fori_loop bound is the row's page count), so a
    long ``max_seq_len`` costs no bandwidth per step and KV capacity is
    pooled, not reserved per slot.  Page 0 is scratch: a zeroed table
    points there and it absorbs every padded write.  Export / import /
    the prefix cache name whole pages ``[:, page]`` across all layers.
  - Slotless prefill.  Prefill runs per admission WAVE: prompts
    sharing a power-of-two length bucket run as one batched forward
    (one compile per (bucket, wave-size) pair) that writes their K/V
    straight into freshly allocated pages and samples each first token
    inside the same jit — *before* any decode slot frees
    (prefill-ahead).  In a bucket some prompt of which can leave a
    chunk of positions out (models/gpt.py ``PREFILL_CHUNK``; buckets
    double, so that is one of more than two chunks: ``_skips_pad``;
    shorter buckets are one pass, as ever) the program is told each
    prompt's real length and does the work of the wave's LONGEST
    prompt, not of its bucket: each layer's token-wise work runs on
    the chunks that prompt reaches, in one loop body whose trip count
    is data, and the flash kernel leaves out each row's query spans
    past its own length.  What a page then holds
    past a real prompt's end is the right-pad's rows where a chunk
    computed them and zeros where none did: finite (the decode kernel
    multiplies a masked probability of 0 by it), and overwritten by a
    decode write before a row's length makes it visible, so padding
    needs no masking.  ``prefill_padded_tokens`` counts what was
    computed.
  - Install with the prefill.  A request admitted while a slot is
    free takes it at once: the block dispatched right behind its
    prefill wave installs it, its first token handed from the wave's
    output to the block's install array ON THE DEVICE (one tiny
    program a wave, ``engine_install_firsts``), everything else of the
    install row (position, temperature, table, state entry) being
    host-known from admission.  The host fetches the wave's first
    tokens where it always did, delivers them, and learns then what it
    installed; a first token that ends the request (eos) evicts the
    row the way any last token does.  So the second token is one
    decode step behind the first on the device, not a block behind
    (``EngineStats.installs_with_prefill``).  Not installed this way:
    an exported request, and one the host knows to end at its first
    token (``max_new_tokens`` 1, a prompt at ``max_seq_len - 1``).
  - The ready queue.  With no slot free, a prefilled request waits
    holding its first token; a freeing slot "installs" it by uploading
    its (token, position, table) row into the block step's device
    state.  Time-to-first-token is bounded by prefill throughput and
    pool capacity, not by slot turnover.
  - State that is not pages.  A model with recurrent layers
    (models/gpt.py LinearAttention, KimiDeltaAttention, Mamba2Mixer)
    keeps, beside its KV pages in the attention layers (latent rows
    where those are latent: the pool and the entries then live in one
    engine, each counted by the layers that hold it), a FIXED-SIZE
    recurrent state a request: two more stacked cache leaves
    ``gdn_state`` [linear layers,
    entries, dk, heads*dv] float32 and ``gdn_conv`` [linear layers,
    entries, (taps-1)*channels/128, 128] (``ssm_state`` [Mamba-2 layers,
    entries, N, heads*P] and ``ssm_conv`` for Mamba-2 layers of either
    block class, a mixer alone or a mixer and a SwiGLU: the model
    declares them, the engine never names their shapes), chained and
    donated with the pool.  A request
    holds one ENTRY of them from admission to finish, allocated and
    freed with its pages (``_free_states`` beside ``_free_pages``;
    admission waits for either); entry 0 is scratch, as page 0 is.  The
    prefill program is told each prompt's REAL length (a recurrence
    would absorb the right-pad attention never sees) and writes the
    prompt's final state to the request's entry, starting from zeros:
    that write is what clears an entry for reuse.  A decode row
    addresses its entry through an ``entries`` array that rides the
    block step's device state and the install upload, as its table
    does; a redirected row points at scratch.  There are ``num_slots +
    1 + _STATE_AHEAD`` entries: every slot, scratch, and that many
    requests prefilled ahead of a slot.  The prefix cache and the
    prefill handoff carry pages only and are refused for such a model.
  - The block step.  One jitted program advances ALL slots UP TO
    ``block_size`` tokens in one loop (``_run_steps``): [N] tokens in,
    [N, K] tokens out, donated pool; tokens, positions, temperatures,
    tables, budgets, eos ids and the rng stay on the device between
    blocks.  Installs from the ready queue upload their current last
    token (host-known since their prefill), so the block's tokens come
    back in a single fetch, with the number of steps it ran.
  - Drafting.  A model with a multi-token-prediction module
    (``cfg.mtp_layers``; models/gpt.py MTPModule) is served with it as
    its own drafter, and a step then yields ONE OR TWO tokens a row
    (``_spec_block_fn``).  A row holds its last confirmed token ``t`` at
    position p, a draft ``d`` for p + 1 and the logits ``d`` was drawn
    from.  A step runs the stack on both positions (the paged kernel at
    two queries a row, both K/V rows written by it), accepts or rejects
    the draft (models/generate.py ``verify_draft``, the row's own
    temperature: the emitted tokens are distributed as the model's own,
    whatever the drafter), advances the row by 1 or 2, runs the module
    on the confirmed positions and draws the next draft.  A rejected
    draft's K/V row at p + 1 is overwritten by the next step; nothing
    is rolled back.  The module's block holds pages in the pool's last
    layer under the same tables; a prefill wave fills them over the
    prompt and yields the first token, the first draft and its logits,
    which stay on the device until the install takes them from there.
    The block's one fetch carries both tokens of every step and how
    many each row emitted.  Refused with it: the prefix cache, the
    prefill handoff (neither carries a draft), recurrent layers (a
    rejected draft would need the state rolled back).
  - A row ends itself.  An install carries the request's budget (the
    tokens it may still emit before ``max_new_tokens`` or
    ``max_seq_len`` ends it) and its eos id.  The step that emits a
    row's last token redirects the row on the device (table -> scratch
    page 0, token 0, position 0, state entry 0; ``_end_step``): no
    later step reads a page, writes a row, moves a state entry or
    routes to an expert for it, and a block ends at the step after
    which no row is live (one step later where the last row ends by
    its eos or on a drafted pair's second token: ``_run_steps``), which
    may be its first (a block dispatched before the host had seen its
    rows' last tokens runs no step).  What
    a block holds past a row's end is zeros or other rows' steps; the
    host truncates as it always did, by the same three rules, and its
    own redirect row for the freed slot still rides the next dispatch
    (idempotent).  Pages are recycled only through dispatches ordered
    after a row's last write (device stream order), so reuse can never
    corrupt a live request.
  - Per-request temperature rides as an [N] array; top_k/top_p are
    engine-static.  The sampler (models/generate.py ``sample_logits``)
    draws only where some row has a temperature: a decode step is told
    the temperatures of its LIVE rows (``_run_steps``: an ended row's
    stays in its slot until the next install), so a step of greedy rows
    computes an argmax and no noise, under the same jit, and a step
    with a sampled row draws for every row as before
    (``stats.block_steps_drawn`` counts those, on the device).

The host loop owns admission/eviction and runs on a plain thread;
``submit`` is loop-aware like serve's ``_BatchQueue.submit`` (awaitable
from an async replica, blocking from a plain thread).
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.profiler import span
from ray_tpu.models.configs import (POOL_KINDS, STATE_KINDS,
                                    TransformerConfig)
from ray_tpu.models.gpt import (GPT, narrowed_logits, output_logits,
                                prefill_positions)
from ray_tpu.ops.moe import PAIR_ROWS
from ray_tpu.serve.frontdoor.prefix import page_digests

# admission waves are padded to the next of these sizes (bounded jit
# specializations per prompt bucket); the top size bounds how many
# prompts one prefill dispatch carries — each dispatch pays a fixed
# host launch latency, so saturation bursts (prefill-ahead admitting a
# whole queue) want wide waves
_WAVE_SIZES = (1, 2, 4, 8, 16, 32)

# state entries beyond one a slot and scratch (module docstring): how
# many requests of a model with recurrent layers may be prefilled and
# waiting for a slot.  An entry is megabytes (20.5 MB at Olmo-Hybrid-7B's
# sizes over 9 layers), so this is small; a request that finds none free
# waits in the queue as it waits for pages
_STATE_AHEAD = 8

# a row's budget where its install names none (``LLMEngine._install``)
_NO_BUDGET = 1 << 30


@dataclasses.dataclass
class GenerationResult:
    tokens: List[int]
    finish_reason: str                    # "eos" | "length"
    prompt_len: int
    time_to_first_token_s: float
    latency_s: float
    # where the time to the first token went, and what came after it:
    # submit -> admitted (popped from the queue with its pages / into a
    # wave) -> first token known -> installed in a decode slot.
    # queue_wait_s + prefill_s == time_to_first_token_s; slot_wait_s is
    # a prefilled request's wait for a slot (inside the gap between its
    # first and second token)
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0
    slot_wait_s: float = 0.0
    # where the time after the first token went, on the same clock:
    # stepping_s + prefill_stall_s + block_tail_s == latency_s -
    # time_to_first_token_s for a request of two tokens or more (all
    # three 0.0 for one that ended at its first).  prefill_stall_s: the
    # device seconds of OTHER requests' prefill waves that ran ahead of
    # decode blocks this one rode, after the block that first stepped
    # it; block_tail_s: from the instant the device produced its last
    # token (step k of its last block, placed in the block's own seconds
    # by k / the steps the block ran) to this result's stamp: the steps
    # other rows took behind it (the block ends with its last live row)
    # and the host's delivery up to this row; stepping_s: the rest
    # (the decode blocks themselves, the first block's wait behind the
    # request's own wave, what the host adds between blocks)
    stepping_s: float = 0.0
    prefill_stall_s: float = 0.0
    block_tail_s: float = 0.0


# re-exported here for engine-local users; defined in ray_tpu.exceptions
# so client-side routers can catch it without importing the jax-heavy
# engine module.  Raised synchronously by import_prefill when the
# import wait queue hits its cap (import_queue_max) — see that method's
# docstring for the FIFO-wait-vs-reject contract.
from ray_tpu.exceptions import KVPoolFullError  # noqa: E402


@dataclasses.dataclass
class PrefillHandoff:
    """A prefilled request packaged for decode on ANOTHER engine.

    ``kv`` is the request's occupied pool pages gathered into ONE
    contiguous host array ``[n_pool_leaves, npages, kv_heads,
    page_size, row]`` (rows exactly as the pool stores them, K|V fused
    or latent: ops/paged_attention.py layout) — one ``jax.device_get``
    round-trip on export, one ``device_put`` + page-table remap on
    import.  Only ``ceil(prompt_len / page_size)`` pages ship: the
    first generated token's K/V is written by the importer's first
    decode step (same invariant as a locally-prefilled install).
    ``kv is None`` when the request finished at its first token
    (``finish_reason`` set) — nothing to decode."""

    kv: Optional[Any]                     # np.ndarray, layout above
    page_size: int
    npages: int
    prompt_len: int
    first_token: int
    max_new_tokens: int
    temperature: float
    eos_id: Optional[int]
    finish_reason: Optional[str] = None   # set: done at first token
    export_ms: float = 0.0                # prefill->gather->fetch wall
    # wire-codec fields (docs/serve_frontdoor.md, serve_handoff_quantize):
    # when ``codec`` is set, ``kv`` holds the ENCODED uint8 wire buffer
    # and shape/dtype/raw_nbytes describe the original array — the serve
    # layer (llm.py) encodes after export and decodes before import, so
    # the engine only ever sees the raw layout.
    codec: Optional[str] = None
    kv_shape: Optional[tuple] = None
    kv_dtype: Optional[str] = None
    raw_nbytes: int = 0

    @property
    def nbytes(self) -> int:
        return int(self.kv.nbytes) if self.kv is not None else 0


@dataclasses.dataclass
class _Request:
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    eos_id: Optional[int]
    deliver: Callable[[bool, Any], None]
    on_token: Optional[Callable[[int], None]]
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    admitted_at: Optional[float] = None   # left the queue (loop thread)
    delivered: bool = False
    export: bool = False                  # deliver a PrefillHandoff
    # chained page-boundary digests of the prompt (frontdoor/prefix.py),
    # computed at submit when the prefix cache is enabled
    digests: Optional[List[str]] = None
    # its entry of the recurrent state leaves, from admission to finish
    # (0: none; a model without recurrent layers never takes one)
    entry: int = 0


@dataclasses.dataclass
class _Import:
    """A PrefillHandoff waiting for pool pages on the decode engine.
    ``need`` is the page count for the FULL generation span (prompt +
    new tokens, capped by the importing engine's max_seq_len) — decode
    writes continue past the shipped prompt pages."""
    handoff: PrefillHandoff
    request: _Request
    need: int


class _Slot:
    __slots__ = ("request", "pos", "out", "last_token", "first_token_at",
                 "installed_at", "pages", "prompt_len", "borrowed",
                 "prefix_entry", "stall_base", "last_step_at")

    def __init__(self, request: _Request, prompt_len: int,
                 first_token: Optional[int],
                 pages: Optional[List[int]] = None,
                 borrowed: int = 0, prefix_entry=None):
        self.request = request
        self.pos = prompt_len            # next write position
        self.installed_at: Optional[float] = None   # took a decode slot
        # LLMEngine._stall_s when the first block that stepped it was
        # delivered (waves ahead of later blocks are other requests'),
        # and the instant the device produced its last token
        self.stall_base: Optional[float] = None
        self.last_step_at: Optional[float] = None
        # None: a prefill wave is computing it (``set_first`` when the
        # host has fetched it)
        if first_token is not None:
            self.set_first(first_token)
        self.pages = pages or []         # physical pool pages owned
        self.prompt_len = prompt_len
        # prefix-cache hit bookkeeping: the first ``borrowed`` entries of
        # ``pages`` are SHARED read-only prefix pages owned by
        # ``prefix_entry`` — never freed here, refcount released instead
        self.borrowed = borrowed
        self.prefix_entry = prefix_entry

    def set_first(self, token: int) -> None:
        self.out = [token]
        self.last_token = token
        self.first_token_at = time.monotonic()


class _PrefixEntry:
    """A retained run of full prompt pages, shared read-only across
    hits.  ``chain[i]`` digests the tokens ``pages[:i+1]`` hold."""

    __slots__ = ("pages", "chain", "refs", "last_used")

    def __init__(self, pages: List[int], chain: List[str]):
        self.pages = pages
        self.chain = chain
        self.refs = 0
        self.last_used = 0


class _Prefilled:
    """A request whose prompt K/V sits in pool pages, or will when the
    prefill wave dispatched for it has run.  Its first token is known
    to the host from the fetch of that wave on; until then it exists on
    the device alone, and a request installed that early (``slot``)
    takes it from there (``source``)."""

    __slots__ = ("slot_state", "table", "source", "slot", "drafted")

    def __init__(self, slot_state: _Slot, table):
        self.slot_state = slot_state     # reused verbatim at install
        self.table = table               # np.int32 [max_pages]
        # a drafting engine: (the wave's (firsts, drafts, draft logits)
        # on the device, this request's row of them), kept until the
        # install takes all three from there
        self.drafted: Optional[tuple] = None
        # (the wave's ``firsts`` on the device, this request's row of
        # it) while the host has not fetched the token
        self.source: Optional[tuple] = None
        # the decode slot it took in the block dispatched behind its
        # prefill wave; None: it waits in ``_ready``
        self.slot: Optional[int] = None


class _Ahead:
    """What was dispatched in front of one decode block (prefill waves,
    imports' scatters), for the account of where that block's interval
    went (``LLMEngine._account_block``).  ``start``: when the device
    turned to it (the previous block's fetch came back, or the dispatch
    where nothing was in flight).  ``watch``: the LAST wave's first
    tokens on the device while the loop has not seen them ready (waves
    run in order, so the last one's end is the end of all); ``seen``:
    the last look that found them not ready.  ``wave_s``: their device
    seconds once the loop has seen the end, None until then.  ``key``:
    the one prefill program this is, where it is one and nothing else;
    ``like``: the seconds such programs took when last seen alone, None
    where one of them never was."""

    __slots__ = ("waves", "key", "like", "watch", "start", "seen",
                 "wave_s")

    def __init__(self, scatters: int, prefills: list, wave_like: dict):
        self.waves = scatters + len(prefills)
        keys = [key for _, _, key in prefills]
        self.key = keys[0] if self.waves == 1 and keys else None
        self.like: Optional[float] = None
        if keys and not scatters and all(k in wave_like for k in keys):
            self.like = sum(wave_like[k] for k in keys)
        self.watch = prefills[-1][0] if prefills else None
        self.start = self.seen = 0.0
        self.wave_s: Optional[float] = None


class EngineStats:
    """Occupancy / throughput counters and the loop thread's time
    accounts, read by benchmarks and /stats.  All cumulative: the
    difference of two snapshots is the window's."""

    def __init__(self):
        self.steps = 0                   # decode steps executed (N-wide)
        self.quanta = 0                  # decode blocks fetched
        # block_size a block fetched: the steps they would have run had
        # none ended with its last live row (block_steps_run / this is
        # how often that engages)
        self.block_steps_offered = 0
        # of ``steps``, those whose sampler drew (some live row had a
        # temperature: models/generate.py sample_logits); counted by
        # the device and fetched with each block's tokens
        self.block_steps_drawn = 0
        # tokens delivered from steps: one a live row a step, or under
        # drafting one or two (so batch_occupancy may pass 1 there)
        self.step_tokens = 0
        self.tokens_generated = 0        # + prefill first tokens
        self.prefills = 0
        # of them, requests stepped by the block dispatched right behind
        # their prefill wave (a slot was free): their second token does
        # not wait a block for the host to learn the first
        self.installs_with_prefill = 0
        self.requests_completed = 0
        self.exports = 0                 # prefill handoffs shipped out
        self.imports = 0                 # prefill handoffs admitted
        self.import_rejects = 0          # pool-full import rejections
        self.prefix_hits = 0             # prefills served from cached pages
        self.prefix_misses = 0           # cache enabled but no usable match
        self.prefix_tokens_saved = 0     # prompt tokens NOT re-prefilled
        self.prefix_evictions = 0        # retained runs evicted (LRU/space)
        self.prefill_waves = 0           # prefill programs dispatched
        self.prefill_prompt_tokens = 0   # real prompt tokens in them
        # what they computed: wave x bucket, or where the program left
        # out the chunks past the wave's longest prompt (models/gpt.py
        # prefill_positions), wave x what it ran
        self.prefill_padded_tokens = 0
        # dropless expert layers (ops/moe.py), counted on the device over
        # the rows that hold a request and fetched with each block's
        # tokens: a layer step is one expert layer in one decode step
        self.moe_layer_steps = 0
        self.moe_experts_touched = 0     # experts with >= 1 pair, summed
        # a model that holds a SHARE of its experts (cfg.moe_experts_held):
        # the (token, choice) pairs its prefill programs' grouped expert
        # products were given, every expert layer, and the pair rows
        # they ran over (slabs run x slab: ops/moe.py dropless_experts);
        # each program counts its own, fetched behind its first tokens
        self.moe_prefill_pairs = 0
        self.moe_prefill_pairs_run = 0
        # pages the decode kernel read over delivered tokens, every
        # layer (decode_pages_read: times a page's bytes in one layer it
        # is the kernel's HBM traffic); of them the window layers' part
        # (window_pages_read), and what those layers left out because it
        # lies wholly behind the window (window_pages_skipped): read +
        # skipped is what the lengths alone would have read there
        self.decode_pages_read = 0
        self.window_pages_read = 0
        self.window_pages_skipped = 0
        # rows the decode kernel wrote into the pool (the step's token,
        # into the row's tail page) over delivered tokens, every pool
        # layer; a pool layer step is one pool layer in one decode step.
        # Their ratio is the rows a layer step writes: the rows that
        # hold a request, not the batch.  Host arithmetic like the pages
        self.decode_rows_written = 0
        self.pool_layer_steps = 0
        # a drafting engine (module docstring): drafts verified by steps
        # whose row was delivered, and of them those that stood.  Such a
        # step reads its pages with two queries and writes two rows a
        # pool layer (decode_pages_read, decode_rows_written), and
        # step_tokens / drafts_proposed is the tokens a live row a step
        self.drafts_proposed = 0
        self.drafts_accepted = 0
        # recurrent layers of either class (gated delta, Mamba-2; the
        # names are the first class's): a layer step is one such layer
        # in one decode step; gdn_state_rows sums over them the rows
        # whose token was delivered, each of which had its state entry
        # read and written once by that layer step.  Host arithmetic,
        # once a row a block, as the page counts are
        self.gdn_layer_steps = 0
        self.gdn_state_rows = 0
        # two high-water marks (not cumulative: the larger of two
        # snapshots is the later one's; ``reset_peaks`` starts them
        # again): the most rows whose token ONE decode step delivered
        # (a block's first step: rows are installed between blocks and
        # only end inside one), which is what a burst does to the
        # recurrent layers' state traffic, and the most state entries
        # held at once, slots and requests prefilled ahead of one, to
        # set against ``state_entries - 1``
        self.live_rows_max = 0
        self.state_entries_max = 0
        # latent-attention layers (a latent pool): a layer step is one
        # such layer in one decode step; mla_context_tokens sums over
        # them the cached positions the absorbed kernel read for rows
        # whose token was delivered (a step at position p reads p + 1
        # rows).  Host arithmetic, once a row a block
        self.mla_layer_steps = 0
        self.mla_context_tokens = 0
        # seconds of the loop thread, advanced at each phase's end
        # (_Phase): loop_s is its whole life, the rest are parts of it.
        # 1 - fetch_wait_s / (loop_s - idle_wait_s) is the share of its
        # working time the host was NOT waiting for the device
        self.loop_s = 0.0
        self.idle_wait_s = 0.0           # blocked: nothing to do
        self.fetch_wait_s = 0.0          # blocked: device -> host fetches
        self.deliver_s = 0.0             # per-token bookkeeping, callbacks
        self._loop_mark: Optional[float] = None
        # the time between first and last token, summed over finished
        # requests of two tokens or more (GenerationResult has the
        # parts): prefill_stall_row_s / decode_row_s is the share of
        # decode time spent behind other requests' prompts
        self.decode_row_s = 0.0
        self.prefill_stall_row_s = 0.0
        self.block_tail_row_s = 0.0
        # (device seconds of prefill waves and import scatters the loop
        # has accounted for, every wave once; since when waves have been
        # running that it has not yet seen the end of; the last of them's
        # first tokens on the device; what such waves took before): one
        # tuple, swapped whole, so that a snapshot from another thread
        # counts waves under way up to now, and never twice
        self._wave: tuple = (0.0, None, None, 0.0)

    @property
    def prefill_wave_s(self) -> float:
        return self._wave[0]

    def reset_peaks(self) -> None:
        """Start the two high-water marks again (a benchmark does so
        before its window)."""
        self.live_rows_max = self.state_entries_max = 0

    @property
    def block_steps_run(self) -> int:
        """``steps``, under the name that pairs with
        ``block_steps_offered``."""
        return self.steps

    def occupancy(self, num_slots: int) -> float:
        """Fraction of step-slots that produced a delivered token (a
        slot nobody holds, or whose row has ended, counts against it)."""
        return (self.step_tokens / (self.steps * num_slots)
                if self.steps else 0.0)

    def snapshot(self, num_slots: int) -> dict:
        wave_s, since, watch, like = self._wave
        if since is not None:
            # still running: all of it up to now; ended at a moment the
            # loop has not seen yet (it is blocked behind the block, or
            # about to look): no more than such waves took before
            under_way = max(0.0, time.monotonic() - since)
            wave_s += (under_way if not watch.is_ready()
                       else min(under_way, like))
        return {
            "steps": self.steps,
            "step_tokens": self.step_tokens,
            "drafts_proposed": self.drafts_proposed,
            "drafts_accepted": self.drafts_accepted,
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "installs_with_prefill": self.installs_with_prefill,
            "requests_completed": self.requests_completed,
            "batch_occupancy": round(self.occupancy(num_slots), 4),
            "exports": self.exports,
            "imports": self.imports,
            "import_rejects": self.import_rejects,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "prefix_evictions": self.prefix_evictions,
            "quanta": self.quanta,
            "block_steps_run": self.block_steps_run,
            "block_steps_offered": self.block_steps_offered,
            "block_steps_drawn": self.block_steps_drawn,
            "prefill_waves": self.prefill_waves,
            "prefill_prompt_tokens": self.prefill_prompt_tokens,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "moe_layer_steps": self.moe_layer_steps,
            "moe_experts_touched": self.moe_experts_touched,
            "moe_prefill_pairs": self.moe_prefill_pairs,
            "moe_prefill_pairs_run": self.moe_prefill_pairs_run,
            "decode_pages_read": self.decode_pages_read,
            "window_pages_read": self.window_pages_read,
            "window_pages_skipped": self.window_pages_skipped,
            "decode_rows_written": self.decode_rows_written,
            "pool_layer_steps": self.pool_layer_steps,
            "gdn_layer_steps": self.gdn_layer_steps,
            "gdn_state_rows": self.gdn_state_rows,
            "live_rows_max": self.live_rows_max,
            "state_entries_max": self.state_entries_max,
            "mla_layer_steps": self.mla_layer_steps,
            "mla_context_tokens": self.mla_context_tokens,
            "loop_s": self.loop_s,
            "idle_wait_s": self.idle_wait_s,
            "fetch_wait_s": self.fetch_wait_s,
            "deliver_s": self.deliver_s,
            "decode_row_s": self.decode_row_s,
            "prefill_stall_row_s": self.prefill_stall_row_s,
            "block_tail_row_s": self.block_tail_row_s,
            "prefill_wave_s": wave_s,
        }


class _Phase:
    """One leaf phase of the engine loop: a span ``engine.<name>`` on the
    profiler's clock (``_private/profiler.py span``, inert unless a
    trace is on) and, where ``account`` names one of EngineStats' time
    accounts, its wall time added there.  Phases do not nest, so an
    idle gap of the device has one phase over it.  Entering returns the
    span: ``set_metadata`` takes what is known only at the end."""

    __slots__ = ("_stats", "_account", "_span", "_t0")

    def __init__(self, stats: EngineStats, name: str,
                 account: Optional[str] = None):
        self._stats = stats
        self._account = account
        self._span = span("engine." + name)

    def __enter__(self):
        self._t0 = time.monotonic()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        st, now = self._stats, time.monotonic()
        if st._loop_mark is not None:
            st.loop_s += now - st._loop_mark
            st._loop_mark = now
        if self._account is not None:
            setattr(st, self._account,
                    getattr(st, self._account) + now - self._t0)
        return False


class LLMEngine:
    """Slot-scheduled KV-cache decoder around a GPT-family checkpoint."""

    def __init__(self, cfg: TransformerConfig, params, *,
                 num_slots: int = 8, max_prompt_len: Optional[int] = None,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 min_prefill_bucket: int = 16, block_size: int = 32,
                 max_seq_len: Optional[int] = None,
                 paged: bool = True, page_size: int = 64,
                 kv_pool_pages: Optional[int] = None,
                 import_queue_max: Optional[int] = None,
                 prefix_cache_pages: int = 0,
                 prefill_wave_tokens: Optional[int] = None):
        if not paged:
            # the keyword outlives the dense engine (removed in PR 30)
            # only until chipbench/ stops passing it (ROADMAP C12)
            raise ValueError(
                "paged=False: the dense engine was removed; LLMEngine "
                "always serves from the paged KV pool")
        # Inference engine owns its own copies of the knobs a server
        # tunes independently of training:
        #  - max_seq_len: the longest sequence a row may reach, hence
        #    the width of a block table and the default pool's size.
        #  - dtype: params are cast to the activation dtype once here;
        #    serving never needs f32 master weights, and keeping them
        #    would re-cast (and re-read) the full parameter set every
        #    decode step.
        if max_seq_len is not None:
            cfg = dataclasses.replace(cfg, max_seq_len=max_seq_len)
        self.cfg = cfg
        self.params = jax.tree.map(
            lambda p: p.astype(cfg.dtype) if hasattr(p, "astype") else p,
            params)
        self.num_slots = num_slots
        self.top_k = top_k
        self.top_p = top_p
        self.max_prompt_len = max_prompt_len or cfg.max_seq_len // 2
        self._min_bucket = min_prefill_bucket
        # the most tokens (wave x bucket) ONE prefill program may carry;
        # more prompts of a bucket than that go in several waves, one
        # after the other.  A wave's activations are its tokens' (32 x
        # 8,192 tokens of queries and expanded keys alone are 13 GB at
        # 32 heads of 192); None: a wave takes up to _WAVE_SIZES[-1]
        # prompts whatever their bucket
        self.prefill_wave_tokens = prefill_wave_tokens
        self.block_size = block_size
        self.page_size = page_size
        self.max_pages = -(-cfg.max_seq_len // page_size)
        # page 0 is the scratch page (zeroed tables point there).  The
        # default pool holds every slot at full length plus one
        # full-length scratch row; a deployment that wants the ready
        # queue to prefill well ahead of slot turnover passes
        # kv_pool_pages (benchmarks/serve_llm.py sizes it per load).
        self.kv_pool_pages = (kv_pool_pages if kv_pool_pages
                              else 1 + (num_slots + 1) * self.max_pages)
        # layers that hold KV pages, and layers that hold a recurrent
        # state entry instead (module docstring)
        self._pool_layers = cfg.layers_of(*POOL_KINDS) + cfg.mtp_layers
        self._state_layers = cfg.layers_of(*STATE_KINDS)
        # the model drafts for itself (module docstring)
        self._drafts = bool(cfg.mtp_layers)
        if self._drafts and (self._state_layers or prefix_cache_pages):
            raise ValueError(
                "a model with a multi-token-prediction module is served "
                "drafting with it: a rejected draft would need a "
                "recurrent layer's state rolled back, and a prefix-cache "
                "hit's suffix prefill has no path that fills the "
                "module's pages over the cached prefix")
        self.state_entries = (num_slots + 1 + _STATE_AHEAD
                              if self._state_layers else 0)
        if self._state_layers and prefix_cache_pages:
            raise ValueError(
                "prefix_cache_pages > 0 on a model with recurrent "
                "layers: a cached page run would need a snapshot of the "
                "recurrent state at its page boundary to resume from, "
                "which the prefix cache does not keep")
        if cfg.kv_lora_rank and prefix_cache_pages:
            raise ValueError(
                "prefix_cache_pages > 0 on a latent-attention model: a "
                "hit's suffix prefill would have to gather the cached "
                "prefix's latent rows out of the pool and expand them "
                "(kv_b) beside the window's own keys, a path "
                "models/gpt.py LatentAttention does not have")
        # layers whose pool row is latent: the absorbed decode kernel's
        self._latent_layers = self._pool_layers if cfg.kv_lora_rank else 0
        self.model = GPT(cfg, decode=True, paged_pages=self.kv_pool_pages,
                         page_size=page_size,
                         state_entries=self.state_entries)
        if self._drafts:
            # same params/cache structure, different (static) attention
            # path: a call of two positions a row is a decode step
            self.model_verify = GPT(cfg, decode=True,
                                    paged_pages=self.kv_pool_pages,
                                    page_size=page_size, verify=True)
        self.stats = EngineStats()
        # the block program also returns the dropless expert layers'
        # load (EngineStats.moe_*); a model without them compiles the
        # program it always did
        self._counts_expert_load = bool(
            cfg.moe_experts and cfg.moe_dropless)
        # a model that holds a share of its experts: a prefill program
        # also returns its expert layers' pair rows
        # (EngineStats.moe_prefill_*), which ``_get_prefill_paged``'s
        # callable keeps here until ``_process_prefill_waves`` reads
        # them; any other model's programs and callers are as they were
        self._counts_pair_rows = bool(
            self._counts_expert_load and cfg.moe_experts_held)
        self._pair_rows: list = []
        # layers whose decode reads stop at the window
        self._window_layers = (
            0 if not cfg.sliding_window else cfg.n_layers
            if cfg.window_layout is None
            else sum(cfg.window_layout[:cfg.n_layers]))
        self._free_states: List[int] = list(
            range(1, self.state_entries))[::-1]

        self._rng = jax.random.PRNGKey(seed)
        self._lock = threading.Condition()
        self._pending: collections.deque = collections.deque()
        self._slots: List[Optional[_Slot]] = [None] * num_slots
        self._free: List[int] = list(range(num_slots))[::-1]
        self._closed = False
        self._thread: Optional[threading.Thread] = None

        # +1 scratch row: the target of padded install rows
        self._rows = num_slots + 1
        # install metadata rows: slots, positions, temps (, entries),
        # then the last two: budget and eos + 1 (``_install``)
        self._meta_rows = 6 if self._state_layers else 5
        self._cache = self._init_cache(self._rows)
        # decode state lives ON DEVICE between blocks (tokens, positions,
        # temps, tables, rng, budgets, eos ids): the host uploads only
        # the small install arrays, and only when something was
        # installed or redirected
        self._state = self._init_state(seed)
        # packed install metadata [_meta_rows, num_slots]: slots row,
        # positions row, temps*1e6 row, ... — one upload per block,
        # cached when empty
        no_meta = np.zeros((self._meta_rows, num_slots), np.int32)
        no_meta[0, :] = num_slots                           # -> scratch
        self._no_admit = (jnp.asarray(no_meta),
                          self._wave_outs(num_slots),
                          jnp.zeros((num_slots, self.max_pages), jnp.int32))
        self._prefill_jit: dict = {}      # (bucket, wave) -> jitted fn
        self._suffix_jit: dict = {}       # (bucket, wave) -> jitted fn
        self._export_jit: dict = {}       # (page bucket, wave) -> fn
        self._import_jit: dict = {}       # (page bucket, wave) -> fn
        self._free_pages: List[int] = list(
            range(1, self.kv_pool_pages))[::-1]
        self._ready: collections.deque = collections.deque()
        self._stale_slots: set = set()    # evicted, redirect pending
        # the loop thread's account of each block's interval (fetch to
        # fetch; ``_account_block``): when the last block's fetch came
        # back, that block's own seconds (its interval less the waves
        # ahead of it), the wave seconds of every block fetched so far
        # (a slot's ``stall_base`` is a mark on it); and what it has
        # measured to reckon by where it cannot see: the seconds each
        # prefill program took when last it ran alone in an iteration,
        # by (bucket, wave size, suffix, positions a row it computed),
        # and a step's share of the last block's own seconds that were
        # measured and not reckoned (blocks differ in length)
        self._fetched_at = 0.0
        self._block_s = 0.0
        self._stall_s = 0.0
        self._wave_like: dict = {}
        self._step_like: Optional[float] = None
        self._imports: collections.deque = collections.deque()
        # admitted-handoff wait-queue bound: beyond it import_prefill
        # rejects SYNCHRONOUSLY (KVPoolFullError) so the caller can
        # route elsewhere.  None (default) queues without bound — a
        # queued import costs one deque entry plus its handoff bytes,
        # and FIFO page allocation cannot wedge (pages free as resident
        # streams complete, exactly the pending-prefill contract).
        # Routers that would otherwise poll a full pool are the reason
        # rejection is a cap, not the default: at saturation, thousands
        # of re-queue round-trips/s cost more decode throughput than
        # the waiting ever could.
        self.import_queue_max = import_queue_max
        # optional observer called with the host-side remap wall (ms)
        # per admitted import wave — the serving layer feeds its
        # handoff-latency histogram without the engine growing a
        # telemetry dependency
        self.on_import_admit: Optional[Callable[[float], None]] = None
        # KV pool leaf identity + handoff shape: pool leaves are
        # [layers, pool_pages, kv_heads, page_size, row]
        # (ops/paged_attention.py layout; the model declares one and
        # its shape is read off it: a latent pool has one KV head and
        # its own row width); _ltot counts the per-layer pools across
        # the cache tree — the leading axis of PrefillHandoff.kv, which
        # both handoff ends must agree on.
        self._pool_tail = tuple(self._cache["kv_pages"].shape[2:])
        self._ltot = sum(
            leaf.shape[0] for leaf in jax.tree.leaves(self._cache)
            if self._is_pool_leaf(leaf))
        # prompt-prefix page cache (docs/serve_frontdoor.md): retained
        # full prompt pages stay OUT of _free_pages, keyed by their
        # chained token digests; hits borrow them read-only and prefill
        # only the suffix.  The budget never exceeds the pool minus one
        # working page.
        self.prefix_cache_pages = max(
            0, min(int(prefix_cache_pages), self.kv_pool_pages - 2))
        self._prefix_lock = threading.Lock()
        self._prefix_index: dict = {}     # digest -> (_PrefixEntry, n)
        # deepest-digest -> entry, insertion-ordered for LRU
        self._prefix_entries: collections.OrderedDict = \
            collections.OrderedDict()
        self._prefix_pages_used = 0
        self._prefix_seq = 0
        if self.prefix_cache_pages:
            # same params/cache structure, different (static) attention
            # path: T>1 windows at nonzero offsets attend back through
            # the pool over borrowed prefix pages
            self.model_prefix = GPT(cfg, decode=True,
                                    paged_pages=self.kv_pool_pages,
                                    page_size=page_size,
                                    prefix_attend=True)

        # every jitted function of the engine is named engine_<what>:
        # a profiler trace shows its program as jit_<name> on the
        # device's ``XLA Modules`` line (docs/observability.md)
        def engine_decode_block(*args):
            return (self._spec_block_fn if self._drafts
                    else self._block_fn)(*args)
        self._block_jit = jax.jit(engine_decode_block,
                                  donate_argnums=(1, 2))

        def engine_install_firsts(lasts, firsts, rows):
            # the block's admit_lasts [num_slots] with the first tokens
            # of ONE prefill wave put in, device to device: rows[n] is
            # the wave's row that install n takes its token from, -1
            # where it takes none.  One program a wave size.  A drafting
            # engine's are triples (token, draft, the draft's logits)
            return jax.tree.map(
                lambda last, first: jnp.where(
                    (rows < 0).reshape((-1,) + (1,) * (last.ndim - 1)),
                    last, first[jnp.maximum(rows, 0)]), lasts, firsts)
        self._install_firsts_jit = jax.jit(engine_install_firsts)

    # ------------------------------------------------------------ jit fns

    def _init_cache(self, batch):
        from ray_tpu.models.generate import init_decode_cache
        return init_decode_cache(self.model, batch)

    def _init_state(self, seed: int):
        state = (jnp.zeros((self._rows,), jnp.int32),     # tokens
                 jnp.zeros((self._rows,), jnp.int32),     # positions
                 jnp.zeros((self._rows,), jnp.float32),   # temps
                 # per-row block tables (zeros -> every page is scratch)
                 jnp.zeros((self._rows, self.max_pages), jnp.int32),
                 jax.random.PRNGKey(seed),                # device rng
                 # tokens each row may still emit, and the token that
                 # ends it (-1: none)
                 jnp.zeros((self._rows,), jnp.int32),
                 jnp.full((self._rows,), -1, jnp.int32))
        if self._state_layers:      # per-row state entries (0: scratch)
            state += (jnp.zeros((self._rows,), jnp.int32),)
        if self._drafts:            # per-row draft and its logits
            state += self._wave_outs(self._rows)[1:]
        return state

    def _wave_outs(self, n: int):
        """Zeros of what a prefill wave of ``n`` prompts yields and an
        install takes: the first tokens [n]; a drafting engine's
        ``(first tokens, drafts, the drafts' logits [n, vocab])``."""
        firsts = jnp.zeros((n,), jnp.int32)
        if not self._drafts:
            return firsts
        return (firsts, firsts,
                jnp.zeros((n, self.cfg.vocab_size), jnp.float32))

    def _sample_fn(self, rng, logits, temps):
        """[B, V] logits + per-row temperature -> [B] token ids
        (models/generate.py sample_logits, array-temperature form, at
        the width the head computed them in)."""
        from ray_tpu.models.generate import sample_logits
        return sample_logits(rng, narrowed_logits(self.cfg, logits),
                             temperature=temps, top_k=self.top_k,
                             top_p=self.top_p)

    def _last_logits(self, model, params, cache, tokens, positions,
                     s_reals, tables, entries=None, skip_pad=True):
        """``(logits [wave, vocab] of each row's last REAL position, the
        updated cache)``; a drafting engine's: ``(logits, cache, the
        stack's hidden states before the final norm)``, for
        ``_first_draft``; where the engine counts them, then the expert
        layers' pair rows (``_sown_pair_rows``).  The head runs on those rows alone: float32
        logits of every position are ``wave x bucket x vocab`` (1.2 GB
        for one 2048-token prompt at a 152k vocabulary), of which one
        row a prompt is read.  ``skip_pad``: the model is told the real
        lengths ``s_reals``, and leaves out what it can of the work past
        them (models/gpt.py ``Block``, ``_prefill_attend``).  ``entries``
        [wave] (a model with recurrent layers, which is always told the
        lengths): where each prompt's final state is written."""
        told = {"lengths": s_reals} if (
            skip_pad or entries is not None) else {}
        if entries is not None:
            told["state_rows"] = entries
        hidden, mut = model.apply(
            {"params": params, "cache": cache}, tokens, positions,
            return_hidden=True, mutable=self._prefill_mutable,
            block_tables=tables, return_prenorm=self._drafts, **told)
        hidden, *prenorm = hidden if self._drafts else (hidden,)
        last = jnp.take_along_axis(
            hidden, (s_reals - 1)[:, None, None], axis=1)[:, 0]
        return (output_logits(self.cfg, params, last), mut["cache"],
                *prenorm, *self._sown_pair_rows(mut))

    def _first_draft(self, params, cache, prenorm, tokens, positions,
                     s_reals, tables, first, temps, rng):
        """A drafting engine's prefill, behind the first token's
        sampling: the prediction module over the prompt, which fills its
        pages (entry i from the stack's hidden state at i, ``prenorm`` as
        ``_last_logits`` returns it, and token i + 1: the prompt's next,
        ``first`` at the last real position), and the first draft, drawn
        from its logits at that position.  ``((first, draft, logits),
        cache)``, then the pair rows where ``_last_logits`` has them."""
        rows = jnp.arange(tokens.shape[0])
        nxt = jnp.roll(tokens, -1, axis=1).at[rows, s_reals - 1].set(first)
        hidden, mut = self.model.apply(
            {"params": params, "cache": cache}, nxt, positions,
            return_hidden=True, mutable=self._prefill_mutable,
            block_tables=tables, mtp_hidden=prenorm, lengths=s_reals)
        logits = output_logits(self.cfg, params, jnp.take_along_axis(
            hidden, (s_reals - 1)[:, None, None], axis=1)[:, 0])
        return ((first, self._sample_fn(rng, logits, temps), logits),
                mut["cache"], *self._sown_pair_rows(mut))

    @property
    def _prefill_mutable(self) -> list:
        """The collections a prefill program lets the model write."""
        return ["cache", PAIR_ROWS] if self._counts_pair_rows else ["cache"]

    def _sown_pair_rows(self, written) -> tuple:
        """Nothing, or where the engine counts them one array: ``[pairs,
        pair rows run]`` int32 summed over the expert layers that sowed
        into the collections ``written`` (one leaf a layer, or one a
        scanned stack, ``[layers, 2]``)."""
        if not self._counts_pair_rows:
            return ()
        return (sum((leaf.reshape(-1, 2).sum(axis=0) for leaf in
                     jax.tree.leaves(written.get(PAIR_ROWS, ()))),
                    jnp.zeros((2,), jnp.int32)),)

    def _keeps_pair_rows(self, program):
        """``program`` as its callers know it, ``-> (first tokens,
        cache)``: where it also returns its pair rows they are kept for
        ``_count_pair_rows``, on the device."""
        if not self._counts_pair_rows:
            return program

        def run(*operands):
            first, cache, rows = program(*operands)
            self._pair_rows.append(rows)
            return first, cache
        run.lower = program.lower
        return run

    def _count_pair_rows(self) -> None:
        """Fold the pair rows of the prefill programs run so far into
        ``stats`` (a fetch of a few ready integers a program)."""
        kept, self._pair_rows = self._pair_rows, []
        for pairs, run in jax.device_get(kept):
            self.stats.moe_prefill_pairs += int(pairs)
            self.stats.moe_prefill_pairs_run += int(run)

    def _get_prefill_paged(self, bucket: int, wave: int):
        """Slotless prefill: prompts write straight into pool pages via
        the model's paged path (the T>1 case of _decode_attend_paged);
        the per-row last REAL logit samples the first token in-jit.
        Donates the pool cache (it chains through every engine call)."""
        fn = self._prefill_jit.get((bucket, wave))
        if fn is None:
            def engine_prefill(params, cache, packed, tables, rng):
                # packed [wave, bucket+2]: prompt tokens | s_real | temp*1e6
                # (| state entry, a model with recurrent layers)
                tokens = packed[:, :bucket]
                s_reals = packed[:, bucket]
                temps = packed[:, bucket + 1].astype(jnp.float32) / 1e6
                b, s = tokens.shape
                positions = jnp.broadcast_to(jnp.arange(s), (b, s))
                last, cache, *more = self._last_logits(
                    self.model, params, cache, tokens, positions, s_reals,
                    tables, packed[:, bucket + 2] if self._state_layers
                    else None, skip_pad=self._skips_pad(bucket))
                if self._drafts:
                    rng, sub = jax.random.split(rng)
                    first, cache, *rows = self._first_draft(
                        params, cache, more[0], tokens, positions, s_reals,
                        tables, self._sample_fn(rng, last, temps), temps,
                        sub)
                    if rows:    # the stack's expert layers, the module's
                        rows = [more[1] + rows[0]]
                    return first, cache, *rows
                return self._sample_fn(rng, last, temps), cache, *more
            fn = self._prefill_jit[(bucket, wave)] = self._keeps_pair_rows(
                jax.jit(engine_prefill, donate_argnums=(1,)))
        return fn

    def _get_prefill_suffix(self, bucket: int, wave: int):
        """Prefix-cache hit prefill: like _get_prefill_paged but each
        row's window starts at a per-row offset (the cached page-aligned
        prefix length) and attends back through the pool — leading block
        table entries are BORROWED read-only prefix pages, the scatter
        touches only the fresh suffix pages past them (positions//ps >=
        the borrow count, offsets are page-aligned by construction)."""
        fn = self._suffix_jit.get((bucket, wave))
        if fn is None:
            def engine_prefill_suffix(params, cache, packed, tables, offs,
                                      rng):
                # packed [wave, bucket+2]: suffix tokens|s_real|temp*1e6
                tokens = packed[:, :bucket]
                s_reals = packed[:, bucket]
                temps = packed[:, bucket + 1].astype(jnp.float32) / 1e6
                b, s = tokens.shape
                positions = offs[:, None] + jnp.broadcast_to(
                    jnp.arange(s), (b, s))
                # windows at an offset, attending through the pool:
                # every position computed, as counted
                last, cache, *rows = self._last_logits(
                    self.model_prefix, params, cache, tokens, positions,
                    s_reals, tables, skip_pad=False)
                return self._sample_fn(rng, last, temps), cache, *rows
            fn = self._suffix_jit[(bucket, wave)] = self._keeps_pair_rows(
                jax.jit(engine_prefill_suffix, donate_argnums=(1,)))
        return fn

    def _is_pool_leaf(self, leaf) -> bool:
        """A cache leaf holding the shared KV page pool: [layers,
        pool_pages, kv_heads, page_size, row], stacked over the
        layers whether or not the model scans them.  Any other cache
        leaf is handoff-irrelevant."""
        return (leaf.ndim == 5
                and leaf.shape[1] == self.kv_pool_pages
                and tuple(leaf.shape[2:]) == self._pool_tail)

    def _page_bucket(self, n: int) -> int:
        """Power-of-two page-count bucket: bounds the gather/scatter jit
        specializations the same way _bucket bounds prefill shapes."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_pages)

    def _get_export(self, bucket: int, wave: int):
        """Gather jit: pull ``wave`` requests' pool pages (``bucket``
        page slots each, pad slots point at scratch page 0) into ONE
        contiguous [wave, ltot, bucket, kvh, ps, 2hd] device array —
        fetched with a single device_get per export group.  Read-only
        on the cache (no donation): dispatched after this iteration's
        block step, so it reads the chained cache value in stream
        order, before any later dispatch can recycle the pages."""
        fn = self._export_jit.get((bucket, wave))
        if fn is None:
            def engine_kv_export(cache, idx):
                flat = idx.reshape(-1)                  # [wave*bucket]
                parts = []
                for leaf in jax.tree.leaves(cache):
                    if not self._is_pool_leaf(leaf):
                        continue
                    g = jnp.take(leaf, flat, axis=1).reshape(
                        (leaf.shape[0], wave, bucket) + self._pool_tail)
                    parts.append(jnp.moveaxis(g, 1, 0))
                return jnp.concatenate(parts, axis=1)
            fn = self._export_jit[(bucket, wave)] = jax.jit(
                engine_kv_export)
        return fn

    def _get_import(self, bucket: int, wave: int):
        """Scatter jit: the inverse remap — land a handoff's pages at
        freshly allocated LOCAL physical pages (pad rows/slots target
        scratch page 0, which absorbs garbage by contract).  Donates
        the cache like every other engine cache transform."""
        fn = self._import_jit.get((bucket, wave))
        if fn is None:
            def engine_kv_import(cache, kv, idx):
                # kv [wave, ltot, bucket, kvh, ps, 2hd]; idx [wave, bucket]
                flat = idx.reshape(-1)
                leaves, treedef = jax.tree_util.tree_flatten(cache)
                out = []
                off = 0                     # ltot cursor (trace-static)
                for leaf in leaves:
                    if not self._is_pool_leaf(leaf):
                        out.append(leaf)
                        continue
                    lc = leaf.shape[0]
                    src = jnp.moveaxis(kv[:, off:off + lc], 1, 0)
                    src = src.reshape((lc, wave * bucket)
                                      + self._pool_tail)
                    out.append(leaf.at[:, flat].set(
                        src.astype(leaf.dtype)))
                    off += lc
                return jax.tree_util.tree_unflatten(treedef, out)
            fn = self._import_jit[(bucket, wave)] = jax.jit(
                engine_kv_import, donate_argnums=(0,))
        return fn

    def _install(self, state, admit_meta, admit_lasts, admit_tables):
        """What a block program does first: the installs, scattered into
        the device state.  admit_meta is one packed [_meta_rows,
        num_slots] i32 upload (slots, positions, temps*1e6, a recurrent
        model's state entries; then each install's budget, the tokens it
        may still emit, 0: no bound, and its eos id + 1, 0: none: a
        caller that fills in neither steps its rows until it redirects
        them), padded so every block reuses one compiled program (pad
        slots point at the scratch row).  admit_lasts holds each
        install's CURRENT last token (a drafting engine's: with its
        draft and the draft's logits): uploaded where the host knows it,
        put there on the device where its prefill wave runs just ahead
        of this block (_dispatch_block), so nothing extra is fetched;
        redirect rows (evicted slots) are just installs of (token 0,
        position 0, zero table -> scratch page).  A row installed ON its
        eos (a first token the host has not seen yet) ends here.
        Returns ``(rows, temps, eos, rng, keys)``: ``rows`` the dict of
        what a step changes (``_end_step``), ``keys`` a sampling key a
        step."""
        tokens, positions, temps, tables, rng, remaining, eos, *rest = state
        a_slots = admit_meta[0]

        def put(old, new):
            return old.at[a_slots].set(new)
        rows = {}
        if self._drafts:
            tokens, rows["drafts"], rows["q_logits"] = (
                put(old, new) for old, new in zip((tokens, *rest),
                                                  admit_lasts))
        else:
            tokens = put(tokens, admit_lasts)
            if rest:          # a redirect row's entry is scratch (0)
                rows["entries"] = put(rest[0], admit_meta[3])
        temps = put(temps, admit_meta[2].astype(jnp.float32) / 1e6)
        eos = put(eos, admit_meta[-1] - 1)
        rows.update(
            positions=put(positions, admit_meta[1]),
            tables=put(tables, admit_tables),
            remaining=put(remaining, jnp.where(
                admit_meta[-2] > 0, admit_meta[-2], _NO_BUDGET)))
        rows = self._end_step(rows, tokens, 0, tokens == eos)
        rng, sub = jax.random.split(rng)
        return (rows, temps, eos, rng,
                jax.random.split(sub, self.block_size))

    @staticmethod
    def _pack_state(rows, temps, eos, rng):
        """The device state in ``_init_state``'s order, from what
        ``_install`` took it apart into."""
        return (rows["tokens"], rows["positions"], temps, rows["tables"],
                rng, rows["remaining"], eos,
                *(rows[k] for k in ("entries", "drafts", "q_logits")
                  if k in rows))

    def _end_step(self, rows, nxt, moved, hit_eos):
        """The end of a decode step, every row at once.  A row whose
        table starts at scratch page 0 holds no request (never
        installed, redirected after eviction, or ended by this very
        function).  The model's Block derives the same mask from the
        tables and its decode kernels read nothing, write nothing, move
        no state entry and route to no expert for such a row (PERF.md,
        PR 27, PR 32, PR 45).  It is stepped AT POSITION 0, so that a
        reader without the mask reads one page of it, not
        ceil((position+1) / page_size): 28 idle rows left to walk to
        max_seq_len did twice the work of the whole model (PERF.md, PR
        25).  A live row has emitted ``moved`` tokens (1, or a drafting
        step's 1 or 2: an overshoot of its budget by one is the host's
        to truncate), the last of them ``nxt``, one of them its eos
        where ``hit_eos``: with its budget spent or its eos out it does
        for itself what the host's redirect install does a block later
        (table to scratch, token 0, position 0, state entry 0), and no
        later step of this block or the next serves it."""
        live = rows["tables"][:, 0] != 0
        remaining = rows["remaining"] - jnp.where(live, moved, 0)
        ended = live & (hit_eos | (remaining <= 0))
        stays = live & ~ended
        # where a row whose install named no budget stops walking (a
        # drafting row's draft position must exist too)
        top = self.cfg.max_seq_len - (2 if self._drafts else 1)
        out = dict(
            rows, remaining=remaining, tokens=jnp.where(stays, nxt, 0),
            positions=jnp.where(
                stays, jnp.minimum(rows["positions"] + moved, top), 0),
            tables=jnp.where(ended[:, None], 0, rows["tables"]))
        if "entries" in rows:
            out["entries"] = jnp.where(ended, 0, rows["entries"])
        return out

    def _run_steps(self, one, rows, temps, cache, keys, outs: int):
        """The block's loop, its trip count the device's to decide:
        ``one(rows, cache, key, live_temps) -> (rows, cache, out, load)``
        while a row is live, ``block_size`` times at most.  Whether to go
        on is settled from what the rows held BEFORE the step just run:
        some live row had more than that step's token left of its
        budget.  A condition on the step's own outcome (any table still
        off scratch) makes the loop wait for the step at its every turn,
        16-30 us a step on the v5e (PERF.md, PR 48); this one is as free
        as a scan's counter, and exact wherever budgets end the rows.
        Where the last row ends by its eos, or by the second token of a
        drafted pair, one more step runs for nobody and the loop ends
        behind it.  ``live_temps`` is settled before the step too:
        ``temps`` with 0 for the rows that hold no request at its start
        (an ended row's temperature stays in its slot until the next
        install), which is what the step's sampler is told, so that it
        draws for live rows' sake alone (models/generate.py
        ``sample_logits``).  ``out`` is ``outs`` int32 [rows] arrays a
        step, written at the step's index into [block_size, rows] buffers
        (zeros past the last step run); ``load`` the step's expert load
        (``_expert_load``; () without).  The cache and the rows ride the
        loop's carry, donated and in place.  Returns the block's ONE
        fetch (each buffer as [rows * block_size], then the steps run,
        how many of them drew, then the expert load's two numbers), the
        rows and the cache."""
        from ray_tpu.models.generate import any_sampled
        zero = jnp.zeros((), jnp.int32)

        def going(carry):
            return (carry[0] < self.block_size) & carry[-1]

        def body(carry):
            step, rows, cache, bufs, drawn, loads, _ = carry
            live = rows["tables"][:, 0] != 0
            more = jnp.any(live & (rows["remaining"] > 1))
            live_temps = jnp.where(live, temps, 0.0)
            rows, cache, out, load = one(rows, cache, keys[step], live_temps)
            return (step + 1, rows, cache,
                    tuple(b.at[step].set(o) for b, o in zip(bufs, out)),
                    drawn + any_sampled(live_temps).astype(jnp.int32),
                    tuple(a + b for a, b in zip(loads, load)), more)

        steps, rows, cache, bufs, drawn, loads, _ = jax.lax.while_loop(
            going, body, (
                zero, rows, cache,
                (jnp.zeros((self.block_size, self._rows), jnp.int32),) * outs,
                zero, (zero, zero) if self._counts_expert_load else (),
                jnp.any(rows["tables"][:, 0] != 0)))
        return jnp.concatenate(
            [b.T.reshape(-1) for b in bufs]
            + [jnp.stack([steps, drawn, *loads])]), rows, cache

    def _block_fn(self, params, cache, state, admit_meta, admit_lasts,
                  admit_tables):
        """At most block_size decode steps (``_run_steps``): one
        dispatch, ONE fetch of the [rows * K] token block and the steps
        run, and all decode state (per-row block tables, budgets and eos
        ids included) chained on device.  The installs are scattered in
        first (``_install``); each step then ends the rows that emitted
        their last token (``_end_step``), and the block ends with its
        last live row."""
        rows, temps, eos, rng, keys = self._install(
            state, admit_meta, admit_lasts, admit_tables)
        load = self._counts_expert_load

        def one(rows, cache, key, live_temps):
            live = rows["tables"][:, 0] != 0
            logits, mut = self.model.apply(
                {"params": params, "cache": cache}, rows["tokens"][:, None],
                rows["positions"][:, None], block_tables=rows["tables"],
                mutable=["cache", "intermediates"] if load else ["cache"],
                **({"state_rows": rows["entries"]} if "entries" in rows
                   else {}))
            nxt = self._sample_fn(key, logits[:, -1], live_temps)
            return (self._end_step(rows, nxt, 1, nxt == eos), mut["cache"],
                    (nxt,), self._expert_load(mut["intermediates"], live)
                    if load else ())

        combined, rows, cache = self._run_steps(
            one, rows, temps, cache, keys, 1)
        return combined, self._pack_state(rows, temps, eos, rng), cache

    def _spec_block_fn(self, params, cache, state, admit_meta, admit_lasts,
                       admit_tables):
        """``_block_fn`` of a drafting engine (module docstring): its
        steps yield ONE OR TWO tokens a row.  The state holds
        each row's draft and the logits it was drawn from besides;
        ``admit_lasts`` is the installs' ``(last token, draft, logits)``.
        A step: the stack over (last token at p, draft at p + 1), one
        pass (``model_verify``: the paged kernel at two queries a row
        writes both K/V rows); ``verify_draft``; the module over both
        positions with the tokens now known to follow them (its second
        entry is junk where the draft fell, and overwritten by the next
        step's first, as the stack's row at p + 1 is); the next draft
        from the module's logits at the row's new last position.  The
        block's ONE fetch is ``[first | second | count]``, ``rows *
        block_size`` each (then ``_run_steps``' counters): ``count`` 1
        or 2, ``second`` junk where it is 1."""
        from ray_tpu.models.generate import verify_draft
        rows, temps, eos, rng, keys = self._install(
            state, admit_meta, admit_lasts, admit_tables)
        load = self._counts_expert_load
        mutable = ["cache", "intermediates"] if load else ["cache"]

        def one(rows, cache, key, live_temps):
            tokens, positions, tables, drafts = (
                rows[k] for k in ("tokens", "positions", "tables", "drafts"))
            live = tables[:, 0] != 0
            k_verify, k_draft = jax.random.split(key)
            at = jnp.stack([positions, positions + 1], axis=1)
            with jax.named_scope("spec_verify"):
                (hidden, prenorm), mut = self.model_verify.apply(
                    {"params": params, "cache": cache},
                    jnp.stack([tokens, drafts], axis=1), at,
                    block_tables=tables, return_hidden=True,
                    return_prenorm=True, mutable=mutable)
                logits = output_logits(self.cfg, params, hidden)
            with jax.named_scope("spec_accept"):
                n, first, second = verify_draft(
                    k_verify, logits[:, 0], logits[:, 1], rows["q_logits"],
                    drafts, temperature=temps, top_k=self.top_k,
                    top_p=self.top_p)
            with jax.named_scope("mtp_draft"):
                drafted, mut2 = self.model_verify.apply(
                    {"params": params, "cache": mut["cache"]},
                    jnp.stack([first, second], axis=1), at,
                    block_tables=tables, return_hidden=True,
                    mtp_hidden=prenorm, mutable=mutable)
                q_logits = output_logits(self.cfg, params, jnp.where(
                    (n == 2)[:, None], drafted[:, 1], drafted[:, 0]))
                drafts = self._sample_fn(k_draft, q_logits, live_temps)
            rows = self._end_step(
                dict(rows, drafts=drafts, q_logits=q_logits),
                jnp.where(n == 2, second, first), n,
                (first == eos) | ((n == 2) & (second == eos)))
            return rows, mut2["cache"], (first, second, n), self._expert_load(
                (mut["intermediates"], mut2["intermediates"]), live
            ) if load else ()

        combined, rows, cache = self._run_steps(
            one, rows, temps, cache, keys, 3)
        return combined, self._pack_state(rows, temps, eos, rng), cache

    def _expert_load(self, intermediates, live):
        """One decode step's expert load over the rows that hold a
        request, from the ``expert_idx`` every dropless expert layer
        sows (``[.., rows, T, k]``, ``T`` 1 or a drafting step's 2,
        stacked by the layer scan): int32
        ``(layer steps with a live row, experts touched summed over
        them)``.  On the v5e 2.3 us of a decode step of 11.4 ms
        (PERF.md, PR 26)."""
        # experts held here: a pair that went to another chip's carries
        # the id past them, which one_hot leaves out
        e = self.cfg.experts_here
        idx = jnp.concatenate([
            leaf.reshape(-1, self._rows, leaf.shape[-2] * leaf.shape[-1])
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                intermediates)
            if "expert_idx" in jax.tree_util.keystr(path)])  # [L, rows, Tk]
        counts = (jax.nn.one_hot(idx, e, dtype=jnp.int32)
                  * live[None, :, None, None].astype(jnp.int32)
                  ).sum(axis=(1, 2))                          # [L, E]
        return ((counts.sum(axis=-1) > 0).sum().astype(jnp.int32),
                (counts > 0).sum().astype(jnp.int32))

    # ------------------------------------------------------------- public

    def warmup(self, prompt_lens=(64,), burst: int = 0) -> None:
        """Compile every jit specialization the given prompt lengths can
        hit (all admission wave sizes per bucket + the block program) so
        no request pays compile latency.  Serve replicas call this at
        init; benchmarks call it before timing.

        ``burst``: additionally push that many 1-token dummy requests
        through the live loop at once, compiling the saturation-burst
        paths the per-function loops can't reach (the combined
        multi-wave fetch concat; its shape depends on the burst
        decomposition)."""
        buckets = sorted({self._bucket(n) for n in prompt_lens})
        rng = jax.random.PRNGKey(0)
        for bucket in buckets:
            # prefill is slotless: any wave size can occur
            for wave in _WAVE_SIZES:
                if wave > self._widest_wave(bucket):
                    break
                packed = np.zeros((wave, self.packed_width(bucket)),
                                  np.int32)
                packed[:, bucket] = 1
                tables = jnp.zeros((wave, self.max_pages), jnp.int32)
                _, self._cache = self._get_prefill_paged(bucket, wave)(
                    self.params, self._cache, jnp.asarray(packed), tables,
                    rng)
        combined, self._state, self._cache = self._block_jit(
            self.params, self._cache, self._state, *self._no_admit)
        np.asarray(combined)   # force completion (and the compile)
        # what hands a wave's first tokens to the block behind it
        # (_dispatch_block): any wave size can occur at any bucket
        none = jnp.full((self.num_slots,), -1, jnp.int32)
        for wave in _WAVE_SIZES:
            self._install_firsts_jit(
                self._no_admit[1], self._wave_outs(wave), none)
        # the programs run above (and by a benchmark that warms its own
        # pairs before it calls this) held no request: not counted
        self._pair_rows.clear()
        if burst:
            plen = max(prompt_lens)

            async def _burst():
                futs = [self.submit([7] * plen, max_new_tokens=1)
                        for _ in range(burst)]
                await asyncio.gather(*futs)

            # mirror submit()'s loop-aware dual path: asyncio.run()
            # raises inside a running event loop (an async serve replica
            # warming up from a coroutine), so drive the burst from a
            # helper thread that owns its own loop instead
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                asyncio.run(_burst())
            else:
                out: dict = {}

                def _runner():
                    try:
                        asyncio.run(_burst())
                    except BaseException as e:  # noqa: BLE001
                        out["err"] = e

                t = threading.Thread(target=_runner,
                                     name="llm-warmup-burst")
                t.start()
                t.join()
                if "err" in out:
                    raise out["err"]

    def packed_width(self, bucket: int) -> int:
        """Columns of a prefill program's ``packed`` operand at
        ``bucket`` (see ``_get_prefill_paged``)."""
        return bucket + (3 if self._state_layers else 2)

    def _refuse_handoff(self) -> None:
        if self._drafts:
            raise ValueError(
                "prefill handoff on a model that drafts with its own "
                "prediction module: a PrefillHandoff carries the stack's "
                "pages and the first token, not the module's pages, the "
                "first draft and the logits it was drawn from")
        if self._state_layers:
            raise ValueError(
                "prefill handoff on a model with recurrent "
                "layers: a PrefillHandoff carries KV pages only, not the "
                "request's recurrent state and convolution tail, so the "
                "importer would decode from an empty state")

    def submit(self, prompt: List[int], *, max_new_tokens: int = 32,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               on_token: Optional[Callable[[int], None]] = None):
        """Enqueue one generation request.

        From inside a running event loop returns an awaitable resolving
        to a GenerationResult (async serve replicas); from a plain
        thread blocks and returns the result (drivers, benchmarks)."""
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt_len:
            raise ValueError(f"prompt len {len(prompt)} > max_prompt_len "
                             f"{self.max_prompt_len}")
        digests = (page_digests(prompt, self.page_size)
                   if self.prefix_cache_pages else None)
        return self._submit_request(
            lambda deliver: _Request(list(prompt), max_new_tokens,
                                     temperature, eos_id, deliver,
                                     on_token, digests=digests),
            self._enqueue)

    async def stream(self, prompt: List[int], *, max_new_tokens: int = 32,
                     temperature: float = 0.0,
                     eos_id: Optional[int] = None):
        """Async-generator submit: yields each generated token id the
        scheduling quantum it is decoded, then the final
        GenerationResult as the last item.  This is the engine end of
        the Serve token-streaming path (serve/llm.py LLMServer.stream →
        replica handle_request_streaming → the caller's
        StreamingObjectRefGenerator): the consumer holds the first
        token while the block decode is still running.

        on_token callbacks fire on the engine thread and are bridged
        onto the calling event loop; the engine's completion delivery
        is loop-ordered after every bridged token, so the final result
        always follows the tokens it summarizes."""
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def on_token(tok: int) -> None:
            loop.call_soon_threadsafe(q.put_nowait, ("token", int(tok)))

        fut = self.submit(prompt, max_new_tokens=max_new_tokens,
                          temperature=temperature, eos_id=eos_id,
                          on_token=on_token)
        fut.add_done_callback(lambda f: q.put_nowait(("done", f)))
        seen = 0
        while True:
            kind, val = await q.get()
            if kind == "token":
                seen += 1
                yield val
                continue
            # raylint: disable=async-blocking -- future already done (this item came from its add_done_callback); result() cannot block
            result = val.result()   # raises engine-fatal errors
            # backstop: any token whose bridge callback lost the race
            # with completion still reaches the consumer, in order
            for tok in result.tokens[seen:]:
                yield int(tok)
            yield result
            return

    # ------------------------------------------- disaggregated handoff API

    def export_prefill(self, prompt: List[int], *,
                       max_new_tokens: int = 32, temperature: float = 0.0,
                       eos_id: Optional[int] = None):
        """Prefill-only submit: run slotless prefill, sample the
        first token, then GATHER the request's pool pages into one
        contiguous host buffer and free them — the request never takes
        a decode slot here.  Resolves to a PrefillHandoff that
        ``import_prefill`` on another engine admits straight into
        decode.  Loop-aware like ``submit`` (awaitable inside an event
        loop, blocking from a plain thread)."""
        self._refuse_handoff()
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt_len:
            raise ValueError(f"prompt len {len(prompt)} > max_prompt_len "
                             f"{self.max_prompt_len}")
        digests = (page_digests(prompt, self.page_size)
                   if self.prefix_cache_pages else None)
        return self._submit_request(
            lambda deliver: _Request(list(prompt), max_new_tokens,
                                     temperature, eos_id, deliver, None,
                                     export=True, digests=digests),
            self._enqueue)

    def import_prefill(self, handoff: PrefillHandoff, *,
                       on_token: Optional[Callable[[int], None]] = None):
        """Admit a PrefillHandoff exported elsewhere: allocate pool
        pages for the full generation span, scatter the shipped prompt
        K/V into them (one upload + page-table remap), and queue the
        request for a decode slot with its first token already known —
        no prefill runs here.  Resolves to the GenerationResult
        (``tokens[0]`` is the handoff's first token; ``on_token`` fires
        only for tokens decoded HERE — the exporter already delivered
        the first one).

        Admission is FIFO like pending prefills: an import whose pages
        aren't free yet WAITS in the engine (one deque entry — pages
        free as resident streams complete, so the wait cannot wedge).
        KVPoolFullError is raised SYNCHRONOUSLY only when
        ``import_queue_max`` is set and the wait queue is full — the
        signal for the router to re-queue against another replica."""
        h = handoff
        self._refuse_handoff()
        if h.finish_reason is not None:
            raise ValueError("handoff already finished at its first "
                             "token; nothing to decode")
        if h.page_size != self.page_size:
            raise ValueError(f"handoff page_size {h.page_size} != engine "
                             f"page_size {self.page_size}")
        kv = np.asarray(h.kv)
        if (kv.ndim != 5 or kv.shape[0] != self._ltot
                or kv.shape[1] != h.npages
                or tuple(kv.shape[2:]) != self._pool_tail):
            raise ValueError(
                f"handoff kv shape {kv.shape} does not match this "
                f"engine's pool layout [{self._ltot}, {h.npages}, "
                f"{self._pool_tail}] — engines must share the model "
                "config and page_size")
        if h.prompt_len >= self.cfg.max_seq_len:
            # an exporter with a larger max_seq_len can produce this;
            # it must fail THIS request, not broadcast-error inside the
            # engine loop (which would fail every resident request)
            raise ValueError(
                f"handoff prompt_len {h.prompt_len} >= this engine's "
                f"max_seq_len {self.cfg.max_seq_len}")
        span = min(h.prompt_len + h.max_new_tokens, self.cfg.max_seq_len)
        need = -(-span // self.page_size)
        if h.npages > need or h.npages > self.max_pages:
            raise ValueError(
                f"handoff ships {h.npages} pages but this engine's "
                f"span allows {min(need, self.max_pages)}")
        if need > self.kv_pool_pages - 1:
            raise ValueError(
                f"handoff needs {need} KV pages; pool holds "
                f"{self.kv_pool_pages - 1}")

        def build(deliver):
            req = _Request([], h.max_new_tokens, h.temperature, h.eos_id,
                           deliver, on_token)
            return _Import(h, req, need)

        def enqueue(imp):
            with self._lock:
                if self._closed:
                    raise RuntimeError("engine closed")
                if (self.import_queue_max is not None
                        and len(self._imports) >= self.import_queue_max):
                    # synchronous, so a full pool costs the caller ONE
                    # exception — not an engine-loop round trip
                    self.stats.import_rejects += 1
                    raise KVPoolFullError(
                        f"import wait queue full "
                        f"({len(self._imports)} >= "
                        f"{self.import_queue_max}); "
                        f"{len(self._free_pages)} pages free of "
                        f"{self.kv_pool_pages - 1}")
                self._imports.append(imp)
                if self._thread is None or not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True, name="llm-engine")
                    self._thread.start()
                self._lock.notify()

        return self._submit_request(build, enqueue)

    async def stream_import(self, handoff: PrefillHandoff):
        """Async-generator import: yields each token decoded HERE the
        quantum it lands (the handoff's first token is NOT re-yielded —
        the prefill side already streamed it), then the final
        GenerationResult.  Decode-pool end of the disaggregated
        streaming path (serve/llm.py LLMServer.decode)."""
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def on_token(tok: int) -> None:
            loop.call_soon_threadsafe(q.put_nowait, ("token", int(tok)))

        fut = self.import_prefill(handoff, on_token=on_token)
        fut.add_done_callback(lambda f: q.put_nowait(("done", f)))
        seen = 0
        while True:
            kind, val = await q.get()
            if kind == "token":
                seen += 1
                yield val
                continue
            # raylint: disable=async-blocking -- future already done (this item came from its add_done_callback); result() cannot block
            result = val.result()   # raises KVPoolFullError / fatal
            # tokens[0] is the handoff's first token; backstop any
            # decoded token whose bridge lost the race with completion
            for tok in result.tokens[1 + seen:]:
                yield int(tok)
            yield result
            return

    def _submit_request(self, build, enqueue):
        """The loop-aware dual delivery path shared by submit /
        export_prefill / import_prefill: build the queue item around a
        deliver callback, enqueue it, and return an awaitable (inside a
        running event loop) or block for the result."""
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            loop = None
        if loop is not None:
            fut = loop.create_future()

            def deliver(ok, value, _loop=loop, _fut=fut):
                def _set():
                    if _fut.done():
                        return
                    (_fut.set_result if ok else _fut.set_exception)(value)
                _loop.call_soon_threadsafe(_set)

            enqueue(build(deliver))
            return fut
        ev = threading.Event()
        out: dict = {}

        def deliver(ok, value):
            out["ok" if ok else "err"] = value
            ev.set()

        enqueue(build(deliver))
        ev.wait()
        if "err" in out:
            raise out["err"]
        return out["ok"]

    def load_snapshot(self) -> dict:
        """Cheap queue/occupancy snapshot feeding per-pool autoscaling
        (serve/replica.py get_metrics -> controller): a prefill pool
        scales off queue depth, a decode pool off slot/ready occupancy."""
        with self._lock:
            return {
                "pending": len(self._pending),
                "imports": len(self._imports),
                "ready": len(self._ready),
                "busy_slots": self.num_slots - len(self._free),
                "free_pages": len(self._free_pages),
                "pool_pages": self.kv_pool_pages,
                "state_entries_in_use": max(
                    0, self.state_entries - 1 - len(self._free_states)),
                "state_entries": self.state_entries,
                "prefix_pages_cached": self._prefix_pages_used,
                "prefix_entries": len(self._prefix_entries),
            }

    def close(self):
        with self._lock:
            self._closed = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)

    # ------------------------------------------------------- loop helpers

    def _enqueue(self, req: _Request):
        with self._lock:
            if self._closed:
                raise RuntimeError("engine closed")
            self._pending.append(req)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="llm-engine")
                self._thread.start()
            self._lock.notify()

    def _phase(self, name: str, account: Optional[str] = None) -> _Phase:
        return _Phase(self.stats, name, account)

    def _bucket(self, n: int) -> int:
        b = self._min_bucket
        while b < n:
            b *= 2
        return min(b, self.cfg.max_seq_len)

    def _skips_pad(self, bucket: int) -> bool:
        """Whether some prompt of ``bucket`` leaves a chunk of it
        uncomputed once the model is told the lengths.  Buckets double,
        so a bucket's prompts are longer than half of it: one of two
        chunks always runs both, and its program stays the one pass.
        A model with recurrent layers computes every position (its
        ``Period`` does not hand the lengths on to its blocks)."""
        shortest = 1 if bucket <= self._min_bucket else bucket // 2 + 1
        return (not self._state_layers
                and prefill_positions(bucket, shortest) < bucket)

    def _widest_wave(self, bucket: int) -> int:
        """The largest wave size whose prompts of ``bucket`` tokens fit
        ``prefill_wave_tokens`` (one prompt always does)."""
        if self.prefill_wave_tokens is None:
            return _WAVE_SIZES[-1]
        return max(w for w in _WAVE_SIZES
                   if w == 1 or w * bucket <= self.prefill_wave_tokens)

    def _wave_chunks(self, items: list):
        """Group (req, payload) pairs by prompt-length bucket and yield
        (bucket, chunk, wave_size) batches — the admission-batching
        policy of the prefill path."""
        by_bucket: dict = {}
        for item in items:
            by_bucket.setdefault(self._bucket(len(item[0].prompt)),
                                 []).append(item)
        for bucket, group in by_bucket.items():
            widest = self._widest_wave(bucket)
            for start in range(0, len(group), widest):
                chunk = group[start:start + widest]
                wave = next(w for w in _WAVE_SIZES if w >= len(chunk))
                yield bucket, chunk, wave

    def _next_key(self):
        self._rng, key = jax.random.split(self._rng)
        return key

    def _count_prefill_wave(self, requests: int, prompt_tokens: int,
                            padded_tokens: int) -> None:
        self.stats.prefills += requests
        self.stats.prefill_waves += 1
        self.stats.prefill_prompt_tokens += prompt_tokens
        self.stats.prefill_padded_tokens += padded_tokens

    def _safe_on_token(self, req: _Request, token: int):
        try:
            req.on_token(token)
        except Exception:       # user callback; never kills the loop
            pass

    @staticmethod
    def _safe_deliver(req: _Request, ok: bool, value) -> None:
        """Exactly-once, exception-proof completion: a client whose
        event loop already closed (or a fatal-path retry of an already
        completed request) must never poison the engine loop or steal
        other submitters' deliveries."""
        if req.delivered:
            return
        req.delivered = True
        try:
            req.deliver(ok, value)
        except Exception:
            pass

    @staticmethod
    def _length_reached(req: _Request, n_out: int, pos: int,
                        max_seq_len: int) -> bool:
        """``n_out`` tokens out and the next write at ``pos``: no more
        are wanted, or fit."""
        return n_out >= req.max_new_tokens or pos + 1 >= max_seq_len

    @classmethod
    def _finish_reason(cls, sl: _Slot, max_seq_len: int) -> Optional[str]:
        req = sl.request
        if req.eos_id is not None and sl.last_token == req.eos_id:
            return "eos"
        if cls._length_reached(req, len(sl.out), sl.pos, max_seq_len):
            return "length"
        return None

    def _deliver_result(self, sl: _Slot, reason: str) -> None:
        req = sl.request
        now = time.monotonic()
        admitted_at = req.admitted_at or req.submitted_at
        queue_wait_s = admitted_at - req.submitted_at
        prefill_s = sl.first_token_at - admitted_at
        # the sum, so that the two parts add up to it exactly
        ttft_s = queue_wait_s + prefill_s
        latency_s = now - req.submitted_at
        stepping_s = stall_s = tail_s = 0.0
        if sl.last_step_at is not None:
            # it ended in a block, so it has two tokens or more and
            # ``_deliver_block`` left its marks: tail and stall as
            # measured, each held inside what is left of the decode
            # time, stepping the remainder
            st = self.stats
            decode_s = max(0.0, latency_s - ttft_s)
            tail_s = min(max(0.0, now - sl.last_step_at), decode_s)
            stall_s = min(max(0.0, self._stall_s - sl.stall_base),
                          decode_s - tail_s)
            stepping_s = max(0.0, decode_s - stall_s - tail_s)
            st.decode_row_s += decode_s
            st.prefill_stall_row_s += stall_s
            st.block_tail_row_s += tail_s
        result = GenerationResult(
            tokens=sl.out, finish_reason=reason,
            prompt_len=sl.pos - len(sl.out) + 1,
            time_to_first_token_s=ttft_s, latency_s=latency_s,
            queue_wait_s=queue_wait_s, prefill_s=prefill_s,
            # a request installed with its prefill was stepping before
            # the host had its first token: it waited for no slot
            slot_wait_s=(0.0 if sl.installed_at is None
                         else max(0.0, sl.installed_at
                                  - sl.first_token_at)),
            stepping_s=stepping_s, prefill_stall_s=stall_s,
            block_tail_s=tail_s)
        self.stats.requests_completed += 1
        self._safe_deliver(req, True, result)

    def _evict(self, i: int, reason: str) -> None:
        """Slot ``i``'s request is done: the slot and what it held go
        back, its result goes out."""
        sl = self._slots[i]
        self._slots[i] = None
        self._free.append(i)
        # the row ended itself on the device at its last token's step
        # (``_end_step``), and a block in flight finds it dead; the
        # redirect row that rides the next dispatch is the host's own
        # record of it.  Pages recycle only through later dispatches, so
        # immediate free is stream-safe (see module docstring), and a
        # row never wrote past its last token: leading pages retained by
        # the prefix cache are its prompt's.
        self._stale_slots.add(i)
        self._prefix_release(sl)
        self._deliver_result(sl, reason)

    @contextlib.contextmanager
    def _prefill_phase(self):
        """The ``dispatch_prefill`` phase, with what it dispatched (the
        prefill counters' growth inside it) as the span's arguments."""
        st = self.stats
        waves, prompt, padded = (st.prefill_waves, st.prefill_prompt_tokens,
                                 st.prefill_padded_tokens)
        with self._phase("dispatch_prefill") as sp:
            yield
            sp.set_metadata(
                waves=st.prefill_waves - waves,
                prompt_tokens=st.prefill_prompt_tokens - prompt,
                padded_tokens=st.prefill_padded_tokens - padded,
                # what a wave writes to the pool a token a layer
                pool_row=self._pool_tail[0] * self._pool_tail[2])

    def _deliver_block(self, block, rows: list, ahead: _Ahead,
                       steps_run: int) -> None:
        """Hand one fetched decode block's tokens to their requests, in
        order, truncating junk past each row's finish.  ``block`` is
        ``[rows, block_size]``, row i's k-th token the one step k gave
        it, of the ``steps_run`` steps the device ran before the block's
        last live row ended (0: it found none, and nothing is delivered);
        a drafting engine's ``[3, rows, block_size]``: each step's
        first token, its second, and how many of the two the row emitted
        (a request may end at the first of a pair; the second is then
        junk like any token past the end).  A step is a step either way
        (``steps``, the layer steps, the place of a request's last step
        in the block's seconds); the tokens, rows written and pages read
        are counted by what the delivered steps did.  ``ahead`` is what
        the device runs meanwhile, in front of the NEXT block: a look at
        it after each row brackets the end of its waves."""
        with self._phase("deliver_block", "deliver_s") as sp:
            st = self.stats
            st.steps += steps_run
            st.quanta += 1
            st.block_steps_offered += self.block_size
            st.pool_layer_steps += steps_run * self._pool_layers
            st.gdn_layer_steps += steps_run * self._state_layers
            st.mla_layer_steps += steps_run * self._latent_layers
            tokens0, done0 = st.step_tokens, st.requests_completed
            drafts0 = st.drafts_proposed, st.drafts_accepted
            if steps_run:
                # rows that still hold their request: each delivers the
                # token of the block's first step.  Counted before any
                # result goes out: whoever holds one may read the counters
                st.live_rows_max = max(st.live_rows_max, sum(
                    self._slots[i] is not None
                    and self._slots[i].request is req for i, req in rows))
            for i, req in rows if steps_run else ():
                sl = self._slots[i]
                if sl is None or sl.request is not req:
                    continue      # evicted earlier (or reused): junk row
                pos0, reason = sl.pos, None
                if self._drafts:
                    # the row's tokens in order, and the step of each
                    count = block[2, i, :steps_run]
                    emitted = np.arange(2)[None, :] < count[:, None]
                    toks = block[:2, i, :steps_run].T[emitted].tolist()
                    step_of = np.repeat(np.arange(steps_run), count)
                else:
                    toks = block[i, :steps_run].tolist()
                for j, tok in enumerate(toks):
                    sl.out.append(tok)
                    sl.last_token = tok
                    sl.pos += 1
                    st.step_tokens += 1
                    st.tokens_generated += 1
                    if sl.request.on_token is not None:
                        self._safe_on_token(sl.request, tok)
                    reason = self._finish_reason(sl, self.cfg.max_seq_len)
                    if reason is not None:
                        break     # rest of the row is junk past eos
                # the step that gave the last delivered token
                k = int(step_of[j]) if self._drafts else j
                if sl.stall_base is None:
                    # its first block: the waves ahead of it (its own,
                    # or those it waited behind for a slot) are not a
                    # stall between its tokens
                    sl.stall_base = self._stall_s
                if self._drafts:
                    # steps 0 .. k, each over its row's position then
                    # and the one after: two queries, two rows written
                    stood = int(count[:k + 1].sum()) - (k + 1)
                    st.drafts_proposed += k + 1
                    st.drafts_accepted += stood
                    self._count_verify_pages(
                        pos0 + 2 + np.cumsum(count[:k + 1]) - count[:k + 1])
                    st.decode_rows_written += 2 * (k + 1) * self._pool_layers
                else:
                    self._count_decode_pages(pos0 + 1, sl.pos)
                    st.decode_rows_written += (
                        sl.pos - pos0) * self._pool_layers
                st.gdn_state_rows += (sl.pos - pos0) * self._state_layers
                # steps at positions pos0 .. pos - 1 read pos0 + 1 .. pos
                st.mla_context_tokens += self._latent_layers * (
                    (sl.pos - pos0) * (sl.pos + pos0 + 1) // 2)
                if reason is not None:
                    # step k + 1 of the block produced its last token;
                    # the steps behind it are other rows'
                    sl.last_step_at = self._fetched_at - self._block_s * (
                        steps_run - 1 - k) / steps_run
                    # counted first: whoever holds the result may read
                    # the counters
                    self._evict(i, reason)
                if ahead.watch is not None:
                    self._look(ahead)
            sp.set_metadata(block=st.quanta,
                            tokens=st.step_tokens - tokens0,
                            finished=st.requests_completed - done0,
                            **({"drafts": st.drafts_proposed - drafts0[0],
                                "accepted": st.drafts_accepted - drafts0[1]}
                               if self._drafts else {}))

    # ------------------------------------- where a block's interval went
    #
    # The device runs, back to back, what the loop dispatched: the waves
    # of iteration i, block i, the waves of iteration i+1, block i+1.  A
    # block's fetch comes back when the block is done, so the interval
    # from one block's fetch to the next is the device's seconds for
    # the waves ahead of the later block plus that block.  Where the
    # waves end inside it the loop learns without touching the device's
    # stream, by looking (``jax.Array.is_ready``) at the last wave's
    # first tokens: after each row it delivers of the block before, and
    # around the fetch of those tokens, which blocks until the wave is
    # done where it is the only one.  Where it saw no end (several
    # waves, whose tokens are joined BEHIND the block) it reckons: each
    # wave as that program went when last it ran alone, or, where one
    # never did (or imports were scattered), the interval's excess over
    # this block's steps at the pace of the last block that was measured.

    def _begin(self, ahead: _Ahead, at: float) -> None:
        """The device turns to what is ahead of the next block."""
        ahead.start = ahead.seen = at
        if ahead.watch is not None:
            # one wave's end is always seen, and at once; several
            # waves' end may not be
            like = float("inf") if ahead.key else ahead.like or 0.0
            self.stats._wave = (self.stats._wave[0], at, ahead.watch, like)

    def _look(self, ahead: _Ahead) -> None:
        now = time.monotonic()
        if ahead.watch.is_ready():
            # done somewhere between the last look and this one
            self._waves_done(ahead, (ahead.seen + now) / 2)
        else:
            ahead.seen = now

    def _waves_done(self, ahead: _Ahead, at: float) -> None:
        ahead.watch = None
        ahead.wave_s = max(0.0, at - ahead.start)
        self.stats._wave = (self.stats._wave[0] + ahead.wave_s, None, None,
                            0.0)

    def _account_block(self, ahead: _Ahead, done: float, steps_run: int):
        """A block's fetch came back at ``done``, ``steps_run`` steps
        long: split the interval since the device turned to it into the
        waves ahead of it and its own seconds.  Returns (interval, wave
        seconds)."""
        interval = max(0.0, done - ahead.start)
        measured = not ahead.waves or ahead.wave_s is not None
        if not measured:
            if ahead.like is not None:
                reckoned = ahead.like
            elif self._step_like is not None:
                reckoned = interval - self._step_like * steps_run
            else:
                reckoned = 0.0
            self._waves_done(
                ahead, ahead.start + min(max(0.0, reckoned), interval))
        elif ahead.key:
            self._wave_like[ahead.key] = ahead.wave_s
        wave_s = min(ahead.wave_s or 0.0, interval)
        self._fetched_at = done
        self._block_s = interval - wave_s
        self._stall_s += wave_s
        if measured and steps_run:
            self._step_like = self._block_s / steps_run
        return interval, wave_s

    def _count_decode_pages(self, first: int, last: int) -> None:
        """``EngineStats.decode_pages_read`` and ``window_pages_*`` for
        one row's delivered steps of ONE query position each
        (``_count_verify_pages`` counts a drafting engine's steps of
        two), which read ``first .. last`` positions: the page
        arithmetic of ``ops/paged_attention.py _tpu_kernel`` (a step
        over ``n`` positions reads pages ``max(0, n - window) //
        page_size`` to ``ceil(n / page_size)``, from page
        0 in a layer without a window) summed in closed form, once a row
        a block.  Host arithmetic on positions the loop already holds:
        it says what the kernel's loop bounds name, not that the kernel
        kept to them (the benchmark's on-chip kernel check and timing,
        and the poisoned-page test, do)."""
        ps, w = self.page_size, self.cfg.sliding_window

        def floors(n):            # sum of k // ps for k = 0 .. n
            q, r = divmod(n, ps)
            return ps * q * (q - 1) // 2 + q * (r + 1) if n > 0 else 0

        by_length = floors(last + ps - 1) - floors(first + ps - 2)
        self._add_pages(by_length,
                        floors(last - w) - floors(max(first, w) - w - 1)
                        if self._window_layers else 0)

    def _add_pages(self, by_length: int, skipped: int) -> None:
        """Pages one row's delivered steps read, to ``EngineStats``:
        ``by_length`` a pool layer by the row's lengths, of which a
        layer with a window left ``skipped`` unread."""
        windowed = self._window_layers
        st = self.stats
        st.window_pages_skipped += skipped * windowed
        st.window_pages_read += (by_length - skipped) * windowed
        st.decode_pages_read += (by_length * self._pool_layers
                                 - skipped * windowed)

    def _count_verify_pages(self, lengths) -> None:
        """``_count_decode_pages`` for a drafting engine's delivered
        steps of one row: ``lengths`` [steps] are the positions each
        step's LAST query saw (its row's position then, + 2).  The
        kernel at two queries a row reads pages ``max(0, n - 1 - window)
        // page_size`` to ``ceil(n / page_size)``: the window counts
        from the FIRST query's position."""
        ps, w = self.page_size, self.cfg.sliding_window
        self._add_pages(
            int((-(-lengths // ps)).sum()),
            int((np.maximum(lengths - 1 - w, 0) // ps).sum())
            if self._window_layers else 0)

    # ------------------------------------------------- prompt-prefix cache
    #
    # All mutation happens on the engine loop thread; _prefix_lock only
    # makes the index/entry maps readable from RPC threads
    # (prefix_digests, load_snapshot).  Pages owned by the cache are in
    # NEITHER _free_pages nor any slot: retention moves ownership from a
    # finishing slot to an entry, eviction moves it back to the free
    # list.  The _free_pages list itself stays loop-thread-confined.

    def prefix_digests(self, limit: int = 64) -> List[str]:
        """Boundary digests of retained prefix runs, newest entries
        first — the replica's advertisement on the controller
        load-publish path (frontdoor/prefix.py contract)."""
        if not self.prefix_cache_pages:
            return []
        out: List[str] = []
        with self._prefix_lock:
            for entry in reversed(self._prefix_entries.values()):
                take = entry.chain[:len(entry.pages)]
                rest = max(0, limit - len(out))
                out.extend(take[-rest:] if rest < len(take) else take)
                if len(out) >= limit:
                    break
        return out[:limit]

    def _prefix_lookup(self, req: _Request):
        """Deepest retained run covering a page-aligned prefix of
        ``req.prompt`` (loop thread, engine lock held).  Returns
        (entry, cover_pages) or None; the hit must leave >= 1 suffix
        token to prefill (it samples the first token) and the padded
        suffix window must still fit max_seq_len."""
        digests = req.digests
        if not digests or not self.prefix_cache_pages:
            return None
        # never borrow the page holding the last prompt token: at least
        # one real token must run through the suffix prefill
        max_cover = (len(req.prompt) - 1) // self.page_size
        with self._prefix_lock:
            for i in range(min(len(digests), max_cover) - 1, -1, -1):
                found = self._prefix_index.get(digests[i])
                if found is None:
                    continue
                entry, cover = found
                cover = min(cover, max_cover, len(entry.pages))
                if cover <= 0:
                    continue
                suffix = len(req.prompt) - cover * self.page_size
                if (cover * self.page_size + self._bucket(suffix)
                        > self.cfg.max_seq_len):
                    continue   # padded window would overflow the span
                entry.refs += 1
                self._prefix_seq += 1
                entry.last_used = self._prefix_seq
                self._prefix_entries.move_to_end(entry.chain[-1])
                return entry, cover
        return None

    def _prefix_evict_locked(self, need: int) -> bool:
        """Evict refs==0 entries, oldest first, until ``need`` cache-
        budget pages are free.  Evicted pages return to _free_pages.
        Caller holds _prefix_lock; loop thread only."""
        if need > self.prefix_cache_pages:
            return False
        victims = [e for e in self._prefix_entries.values()
                   if e.refs == 0]
        vi = 0
        while (self._prefix_pages_used + need > self.prefix_cache_pages
               and vi < len(victims)):
            entry = victims[vi]
            vi += 1
            for d in entry.chain:
                if self._prefix_index.get(d, (None,))[0] is entry:
                    del self._prefix_index[d]
            self._prefix_entries.pop(entry.chain[-1], None)
            self._prefix_pages_used -= len(entry.pages)
            self._free_pages.extend(entry.pages)
            entry.pages = []
            self.stats.prefix_evictions += 1
        return self._prefix_pages_used + need <= self.prefix_cache_pages

    def _prefix_reclaim(self, need_free: int) -> None:
        """Admission pressure valve (loop thread, engine lock held):
        the FIFO head needs ``need_free`` pages the free list doesn't
        have — evict idle retained runs to unblock it rather than
        wedging admission behind the cache."""
        if not self.prefix_cache_pages:
            return
        with self._prefix_lock:
            freed = 0
            for key in list(self._prefix_entries):
                if freed >= need_free:
                    break
                entry = self._prefix_entries[key]
                if entry.refs:
                    continue
                for d in entry.chain:
                    if self._prefix_index.get(d, (None,))[0] is entry:
                        del self._prefix_index[d]
                del self._prefix_entries[key]
                self._prefix_pages_used -= len(entry.pages)
                self._free_pages.extend(entry.pages)
                freed += len(entry.pages)
                entry.pages = []
                self.stats.prefix_evictions += 1

    def _prefix_retain(self, sl: _Slot) -> int:
        """Move a finishing slot's leading full PROMPT pages into the
        cache (loop thread).  Returns how many of sl.pages the cache
        took (they must not be freed); 0 when retention is off, the
        prompt spans < 1 full page, the run is already cached, or the
        budget cannot fit it even after eviction."""
        req = sl.request
        if (not self.prefix_cache_pages or not req.digests
                or sl.borrowed):
            return 0
        n_full = min(sl.prompt_len // self.page_size, len(req.digests),
                     len(sl.pages))
        if n_full <= 0:
            return 0
        chain = req.digests[:n_full]
        with self._prefix_lock:
            known = self._prefix_index.get(chain[-1])
            if known is not None and known[1] >= n_full:
                return 0                    # already resident
            if not self._prefix_evict_locked(n_full):
                return 0
            entry = _PrefixEntry(sl.pages[:n_full], chain)
            self._prefix_seq += 1
            entry.last_used = self._prefix_seq
            for i, d in enumerate(chain):
                self._prefix_index[d] = (entry, i + 1)
            self._prefix_entries[chain[-1]] = entry
            self._prefix_entries.move_to_end(chain[-1])
            self._prefix_pages_used += n_full
        return n_full

    def _prefix_release(self, sl: _Slot) -> None:
        """Free a slot's pages with prefix accounting: borrowed
        prefix pages go back to their entry (refcount), owned pages are
        offered to retention first, the rest return to the pool."""
        kept = self._prefix_retain(sl)
        self._free_pages.extend(sl.pages[max(kept, sl.borrowed):])
        if sl.request.entry:
            self._free_states.append(sl.request.entry)
            sl.request.entry = 0
        if sl.prefix_entry is not None:
            with self._prefix_lock:
                sl.prefix_entry.refs -= 1
            sl.prefix_entry = None
        sl.pages = []
        sl.borrowed = 0

    def _prefix_reset(self) -> None:
        """Engine-fatal recovery: the pool was rebuilt, every retained
        page id is meaningless — drop the cache wholesale."""
        with self._prefix_lock:
            self._prefix_index.clear()
            self._prefix_entries.clear()
            self._prefix_pages_used = 0

    # -------------------------------------------------------- engine loop

    def _pages_needed(self, req: _Request) -> int:
        if req.export:
            # prefill-only: the request never decodes here, so it holds
            # exactly its prompt's pages until the export gather frees
            # them (the importer allocates the full span)
            return -(-len(req.prompt) // self.page_size)
        span = min(len(req.prompt) + req.max_new_tokens,
                   self.cfg.max_seq_len)
        return -(-span // self.page_size)

    def _loop(self):
        """Software-pipelined, with a slotless prefill stage ahead of
        the block: each iteration (1) prefills as many queued prompts as
        the pool allows, (2) gives free slots to ready requests and then
        to the requests just prefilled (their first token still on the
        device) and dispatches the next block, (3) processes the
        PREVIOUS block's fetch, (4) fetches this iteration's prefill
        first-tokens (the device finished them before the
        just-dispatched block).  Block k+1 is dispatched before block
        k's tokens are fetched, so the device never idles on the host's
        fetch round-trip or bookkeeping.  The price is a one-block lag
        of the SLOT, not of the device: a row that finished in block k
        (or at its first token, an install with the prefill) ended
        itself on the device at that step and costs block k+1 nothing
        (``_end_step``; a block k+1 with no other row runs no step),
        but the host learns of it a block later and its slot is free
        for the block after, which the request-identity check in
        _deliver_block makes safe.  There is
        no install lag while a slot is free: a request is stepped by
        the block behind its prefill.  Only a request that found every
        slot taken waits in _ready, for the eviction that frees one.
        TTFT is one prefill round-trip, independent of slot turnover.
        """
        self.stats._loop_mark = time.monotonic()
        inflight = None       # (combined_dev, rows)
        while True:
            with self._lock:
                while (not self._closed and not self._pending
                       and not self._imports and not self._ready
                       and all(s is None for s in self._slots)
                       and inflight is None):
                    with self._phase("wait_work", "idle_wait_s"):
                        self._lock.wait()
                if self._closed:
                    victims = (
                        [s.request for s in self._slots if s is not None]
                        + [pf.slot_state.request for pf in self._ready]
                        + [imp.request for imp in self._imports]
                        + list(self._pending))
                    self._pending.clear()
                    self._ready.clear()
                    self._imports.clear()
                    for req in victims:
                        self._safe_deliver(
                            req, False, RuntimeError("engine closed"))
                    return
                with self._phase("admit") as sp:
                    now = time.monotonic()
                    # imports first (a decode-pool engine's whole intake
                    # is handoffs), FIFO like pending prefills: the head
                    # waits for pages, nothing bypasses it (no
                    # starvation), and pages always free as resident
                    # streams complete — the queue-full rejection happens
                    # synchronously at submit
                    import_todo = []
                    while self._imports:
                        short = (self._imports[0].need
                                 - len(self._free_pages))
                        if short > 0:
                            # idle retained prefixes must not wedge the
                            # FIFO head: the cache yields before admission
                            self._prefix_reclaim(short)
                        if self._imports[0].need > len(self._free_pages):
                            break
                        imp = self._imports.popleft()
                        imp.request.admitted_at = now
                        pages = [self._free_pages.pop()
                                 for _ in range(imp.need)]
                        import_todo.append((imp, pages))
                    todo = []
                    hits = []
                    oversized = []
                    while self._pending:
                        need = self._pages_needed(self._pending[0])
                        if need > self.kv_pool_pages - 1:
                            # can never fit: fail it, do not spin forever
                            oversized.append(self._pending.popleft())
                            continue
                        hit = self._prefix_lookup(self._pending[0])
                        fresh = need - (hit[1] if hit else 0)
                        if fresh > len(self._free_pages):
                            self._prefix_reclaim(
                                fresh - len(self._free_pages))
                        if fresh > len(self._free_pages) or (
                                self._state_layers
                                and not self._free_states):
                            if hit is not None:
                                with self._prefix_lock:
                                    hit[0].refs -= 1
                            break          # FIFO: no bypass, no starvation
                        req = self._pending.popleft()
                        req.admitted_at = now
                        if self._state_layers:
                            req.entry = self._free_states.pop()
                            self.stats.state_entries_max = max(
                                self.stats.state_entries_max,
                                self.state_entries - 1
                                - len(self._free_states))
                        pages = [self._free_pages.pop()
                                 for _ in range(fresh)]
                        if hit is not None:
                            entry, cover = hit
                            self.stats.prefix_hits += 1
                            self.stats.prefix_tokens_saved += \
                                cover * self.page_size
                            hits.append((req, entry.pages[:cover] + pages,
                                         cover, entry))
                        else:
                            if self.prefix_cache_pages and req.digests:
                                self.stats.prefix_misses += 1
                            todo.append((req, pages))
                    sp.set_metadata(
                        admitted=len(todo) + len(hits) + len(import_todo),
                        pending=len(self._pending),
                        free_pages=len(self._free_pages),
                        **({"free_states": len(self._free_states)}
                           if self._state_layers else {}))
            for req in oversized:
                self._safe_deliver(req, False, ValueError(
                    f"request needs {self._pages_needed(req)} KV pages; "
                    f"pool holds {self.kv_pool_pages - 1}"))
            try:
                dispatched_at = time.monotonic()
                # scatter imports BEFORE taking installs: an imported
                # request can land in a free slot this same iteration,
                # and the block step is dispatched after the scatter so
                # stream order covers its page writes
                scatters = 0
                if import_todo:
                    with self._phase("dispatch_import") as sp:
                        sp.set_metadata(requests=len(import_todo))
                        scatters = self._dispatch_import_waves(import_todo)
                new_prefills = []
                if todo or hits:
                    with self._prefill_phase():
                        new_prefills = (self._dispatch_prefill_waves(todo)
                                        + self._dispatch_suffix_waves(hits))
                with self._lock:
                    installs = []
                    while self._free and self._ready:
                        installs.append((self._ready.popleft(),
                                         self._free.pop()))
                    # slots still free (so nothing is left in _ready):
                    # the requests of the waves just dispatched take
                    # them, FIFO, and are stepped by the block that
                    # follows their prefill on the device
                    early = self._install_with_prefill(new_prefills,
                                                       installs)
                ahead = _Ahead(scatters, new_prefills, self._wave_like)
                with self._phase("dispatch_block") as sp:
                    nxt = self._dispatch_block(installs)
                    sp.set_metadata(installs=len(installs), early=early,
                                    active=len(nxt[1]) if nxt else 0)
                if inflight is not None:
                    self._process_block(inflight, ahead)
                else:
                    # nothing in flight: the device turns to this
                    # iteration's work as it is dispatched
                    self._begin(ahead, dispatched_at)
                exports = self._process_prefill_waves(
                    new_prefills, ahead, alone=nxt is None)
                if exports:
                    with self._phase("export") as sp:
                        sp.set_metadata(requests=len(exports))
                        self._process_exports(exports)
                # with what runs ahead of it, for the account of its
                # interval when its fetch comes back
                inflight = None if nxt is None else (*nxt, ahead)
            except Exception as e:   # engine-fatal (OOM, compile error)
                with self._lock:
                    victims = (
                        [s.request for s in self._slots if s is not None]
                        + [pf.slot_state.request for pf in self._ready]
                        + [r for r, _ in todo]
                        + [r for r, _, _, _ in hits]
                        + [imp.request for imp, _ in import_todo]
                        + [imp.request for imp in self._imports]
                        + ([r for _, r in inflight[1]] if inflight else [])
                        + list(self._pending))
                    self._pending.clear()
                    self._ready.clear()
                    self._imports.clear()
                    self._slots = [None] * self.num_slots
                    self._free = list(range(self.num_slots))[::-1]
                    self._free_pages = list(
                        range(1, self.kv_pool_pages))[::-1]
                    self._free_states = list(
                        range(1, self.state_entries))[::-1]
                    self._stale_slots.clear()
                self._prefix_reset()
                inflight = None
                self._step_like = None
                self.stats._wave = (self.stats._wave[0], None, None, 0.0)
                self._cache = self._init_cache(self._rows)
                self._state = self._init_state(0)
                for req in victims:
                    self._safe_deliver(req, False, e)

    def _install_with_prefill(self, waves: list, installs: list) -> int:
        """Give free slots to requests whose prefill wave was dispatched
        this iteration (engine lock held): appended to ``installs`` with
        ``source`` naming where on the device their first token will be.
        Not a request that is exported, nor one the host knows to end at
        its first token whatever that is: neither decodes here.  Returns
        how many."""
        early = 0
        for firsts, metas, _ in waves:
            for row, pf in enumerate(metas):
                if not self._free:
                    break
                req = pf.slot_state.request
                if req.export or self._length_reached(
                        req, 1, len(req.prompt), self.cfg.max_seq_len):
                    continue
                pf.source = (firsts, row)
                pf.slot = self._free.pop()
                installs.append((pf, pf.slot))
                early += 1
        self.stats.installs_with_prefill += early
        return early

    def _dispatch_prefill_waves(self, todo: list) -> list:
        """Batch queued prompts into (bucket, wave) prefill calls that
        write straight into their reserved pages.  Device dispatch only —
        first tokens are fetched later in the iteration.  Returns a
        (firsts, metas, key) a wave, ``key`` naming its program and, as
        its seconds follow them, the positions a row it computes."""
        out = []
        for bucket, chunk, wave in self._wave_chunks(todo):
            packed = np.zeros((wave, self.packed_width(bucket)), np.int32)
            packed[:, bucket] = 1
            tables = np.zeros((wave, self.max_pages), np.int32)
            metas = []
            for r, (req, pages) in enumerate(chunk):
                packed[r, :len(req.prompt)] = req.prompt
                packed[r, bucket] = len(req.prompt)
                packed[r, bucket + 1] = int(req.temperature * 1e6)
                if self._state_layers:
                    packed[r, bucket + 2] = req.entry
                tables[r, :len(pages)] = pages
                metas.append(_Prefilled(
                    _Slot(req, len(req.prompt), None, pages),
                    tables[r].copy()))
            firsts, self._cache = self._get_prefill_paged(
                bucket, wave)(self.params, self._cache,
                              jnp.asarray(packed),
                              jnp.asarray(tables), self._next_key())
            if self._drafts:
                outs, firsts = firsts, firsts[0]    # + drafts, their logits
                for r, pf in enumerate(metas):
                    pf.drafted = (outs, r)
            computed = prefill_positions(
                bucket, max(len(req.prompt) for req, _ in chunk)
            ) if self._skips_pad(bucket) else bucket
            self._count_prefill_wave(
                len(chunk), sum(len(req.prompt) for req, _ in chunk),
                wave * computed)
            out.append((firsts, metas, (bucket, wave, False, computed)))
        return out

    def _dispatch_suffix_waves(self, todo: list) -> list:
        """Prefix-cache hits: batch by SUFFIX-length bucket and run the
        offset prefill — each row's leading table entries are borrowed
        read-only prefix pages, the window starts at the page-aligned
        cover and writes only fresh pages.  Output rides the same
        (firsts, metas, key) shape as _dispatch_prefill_waves."""
        out = []
        by_bucket: dict = {}
        for item in todo:
            req, pages, cover, entry = item
            sfx = len(req.prompt) - cover * self.page_size
            by_bucket.setdefault(self._bucket(sfx), []).append(item)
        for bucket, group in by_bucket.items():
            widest = self._widest_wave(bucket)
            for start in range(0, len(group), widest):
                chunk = group[start:start + widest]
                wave = next(w for w in _WAVE_SIZES if w >= len(chunk))
                packed = np.zeros((wave, bucket + 2), np.int32)
                packed[:, bucket] = 1
                tables = np.zeros((wave, self.max_pages), np.int32)
                offs = np.zeros((wave,), np.int32)
                metas = []
                for r, (req, pages, cover, entry) in enumerate(chunk):
                    c = cover * self.page_size
                    suffix = req.prompt[c:]
                    packed[r, :len(suffix)] = suffix
                    packed[r, bucket] = len(suffix)
                    packed[r, bucket + 1] = int(req.temperature * 1e6)
                    tables[r, :len(pages)] = pages
                    offs[r] = c
                    metas.append(_Prefilled(
                        _Slot(req, len(req.prompt), None, pages, cover,
                              entry), tables[r].copy()))
                firsts, self._cache = self._get_prefill_suffix(
                    bucket, wave)(self.params, self._cache,
                                  jnp.asarray(packed),
                                  jnp.asarray(tables),
                                  jnp.asarray(offs), self._next_key())
                self._count_prefill_wave(
                    len(chunk), int(packed[:len(chunk), bucket].sum()),
                    wave * bucket)
                out.append((firsts, metas, (bucket, wave, True, bucket)))
        return out

    def _process_prefill_waves(self, waves: list, ahead: _Ahead,
                               alone: bool) -> list:
        """Fetch this iteration's prefill first-tokens with ONE combined
        device->host transfer (each fetch is a host sync with a fixed
        latency; a saturation burst dispatches many waves per
        iteration) and complete/queue each request.  Returns the
        export-flagged requests (first token now known) for
        _process_exports.  ``alone``: no block was dispatched behind
        the waves."""
        if not waves:
            return []
        if ahead.watch is not None:
            self._look(ahead)
        with self._phase("fetch_prefill", "fetch_wait_s"):
            if len(waves) == 1:
                host = np.asarray(waves[0][0])
            else:
                host = np.asarray(jnp.concatenate([f for f, *_ in waves]))
        if ahead.watch is not None and (len(waves) == 1 or alone):
            # the fetch waited for the waves' end and for nothing else
            # (several waves' tokens are joined BEHIND the block)
            self._waves_done(ahead, time.monotonic())
        if self._pair_rows:
            self._count_pair_rows()
        off = 0
        exports = []
        with self._phase("deliver_prefill", "deliver_s") as sp:
            for firsts, metas, _ in waves:
                n = firsts.shape[0]
                exports.extend(self._complete_prefills(metas,
                                                       host[off:off + n]))
                off += n
            sp.set_metadata(requests=sum(len(m) for _, m, _ in waves))
        return exports

    def _complete_prefills(self, metas, host) -> list:
        """The host learns a wave's first tokens.  Requests finish here
        if one token was all they wanted, otherwise they join the ready
        queue holding it — unless the block behind the wave already
        steps them (``_install_with_prefill``).  Export-flagged requests
        are returned for the gather stage instead of queueing for a
        local slot."""
        exports = []
        for pf, first in zip(metas, host):
            sl = pf.slot_state
            req = sl.request
            self.stats.tokens_generated += 1
            sl.set_first(int(first))
            pf.source = None
            if req.export:
                exports.append((req, sl))
                continue
            if req.on_token is not None:
                self._safe_on_token(req, int(first))
            reason = self._finish_reason(sl, self.cfg.max_seq_len)
            if pf.slot is not None:
                # stepping since the block behind its wave: a first
                # token that ends it (eos) evicts it as any row's last
                # token does, redirect and all
                if reason is not None:
                    self._evict(pf.slot, reason)
            elif reason is not None:
                # never installed -> no row steps on these pages:
                # free immediately, no redirect needed
                self._prefix_release(sl)
                self._deliver_result(sl, reason)
            else:
                with self._lock:
                    self._ready.append(pf)
        return exports

    def _process_exports(self, exports: list) -> None:
        """Gather exported requests' occupied pages into contiguous
        host buffers — one device dispatch + ONE fetch per
        (page-bucket, wave) group — free the pages, and deliver
        PrefillHandoffs.  Dispatched after this iteration's block step,
        so the gather reads the chained cache in stream order; the
        freed pages recycle only through later dispatches (the standard
        pool invariant)."""
        if not exports:
            return
        groups: dict = {}
        for req, sl in exports:
            reason = self._finish_reason(sl, self.cfg.max_seq_len)
            if reason is not None:
                # done at its first token: nothing to decode anywhere —
                # ship a kv-less handoff the serving layer completes
                # from directly
                self._prefix_release(sl)
                self.stats.requests_completed += 1
                self.stats.exports += 1
                self._safe_deliver(req, True, PrefillHandoff(
                    kv=None, page_size=self.page_size, npages=0,
                    prompt_len=sl.pos, first_token=sl.out[0],
                    max_new_tokens=req.max_new_tokens,
                    temperature=req.temperature, eos_id=req.eos_id,
                    finish_reason=reason))
                continue
            n_occ = -(-sl.pos // self.page_size)   # prompt pages only
            groups.setdefault(self._page_bucket(n_occ),
                              []).append((req, sl, n_occ))
        for bucket, group in groups.items():
            for start in range(0, len(group), _WAVE_SIZES[-1]):
                chunk = group[start:start + _WAVE_SIZES[-1]]
                wave = next(w for w in _WAVE_SIZES if w >= len(chunk))
                t0 = time.monotonic()
                idx = np.zeros((wave, bucket), np.int32)
                for r, (req, sl, n_occ) in enumerate(chunk):
                    idx[r, :n_occ] = sl.pages[:n_occ]
                dev = self._get_export(bucket, wave)(self._cache,
                                                     jnp.asarray(idx))
                host = np.asarray(dev)             # ONE fetch per group
                # amortized per request (the import side divides its
                # wave cost the same way — the stages must be
                # comparable in the handoff-latency histogram)
                ms = round((time.monotonic() - t0) * 1e3 / len(chunk), 3)
                for r, (req, sl, n_occ) in enumerate(chunk):
                    kv = np.ascontiguousarray(host[r, :, :n_occ])
                    # the gather above already read the pages: retention
                    # (prefill-pool hot path) or free, borrow-aware
                    self._prefix_release(sl)
                    self.stats.exports += 1
                    self._safe_deliver(req, True, PrefillHandoff(
                        kv=kv, page_size=self.page_size, npages=n_occ,
                        prompt_len=sl.pos, first_token=sl.out[0],
                        max_new_tokens=req.max_new_tokens,
                        temperature=req.temperature, eos_id=req.eos_id,
                        export_ms=ms))

    def _dispatch_import_waves(self, todo: list) -> int:
        """Scatter admitted handoffs' prompt K/V into their freshly
        allocated pages — one packed upload + one jitted remap per
        (page-bucket, wave) group — and queue them ready-to-install
        with their first token already known.  No prefill compute, no
        fetch: the decode-only admission path.  Returns the scatter
        programs dispatched."""
        scatters = 0
        groups: dict = {}
        for imp, pages in todo:
            groups.setdefault(self._page_bucket(imp.handoff.npages),
                              []).append((imp, pages))
        for bucket, group in groups.items():
            for start in range(0, len(group), _WAVE_SIZES[-1]):
                chunk = group[start:start + _WAVE_SIZES[-1]]
                wave = next(w for w in _WAVE_SIZES if w >= len(chunk))
                t0 = time.monotonic()
                kvbuf = np.zeros(
                    (wave, self._ltot, bucket) + self._pool_tail,
                    dtype=self.cfg.dtype)
                idx = np.zeros((wave, bucket), np.int32)
                for r, (imp, pages) in enumerate(chunk):
                    h = imp.handoff
                    kvbuf[r, :, :h.npages] = np.asarray(h.kv)
                    idx[r, :h.npages] = pages[:h.npages]
                self._cache = self._get_import(bucket, wave)(
                    self._cache, jnp.asarray(kvbuf), jnp.asarray(idx))
                scatters += 1
                if self.on_import_admit is not None:
                    ms = (time.monotonic() - t0) * 1e3 / len(chunk)
                    for _ in chunk:
                        self.on_import_admit(ms)
                for imp, pages in chunk:
                    h = imp.handoff
                    sl = _Slot(imp.request, h.prompt_len, h.first_token,
                               pages)
                    self.stats.imports += 1
                    reason = self._finish_reason(sl, self.cfg.max_seq_len)
                    if reason is not None:
                        # belt-and-braces: a 1-token import finishes
                        # without ever stepping (exporters normally
                        # short-circuit these with finish_reason)
                        self._free_pages.extend(sl.pages)
                        sl.pages = []
                        self._deliver_result(sl, reason)
                        continue
                    table = np.zeros((self.max_pages,), np.int32)
                    table[:len(pages)] = pages
                    with self._lock:
                        self._ready.append(_Prefilled(sl, table))
        return scatters

    def _dispatch_block(self, installs: list):
        """Install requests into free slots, attach redirect rows for
        stale slots, and dispatch one decode block.  A request's
        position, temperature, table, state entry, budget and eos id are
        host-known from its admission on (``_install`` says how each
        rides ``meta``); its last token is too (nothing is fetched),
        except where its prefill wave was dispatched this iteration
        (``pf.source``): that token goes from the wave's output into the
        block's ``admit_lasts`` on the device.  Returns
        (combined_device, rows) or None when no slot is active."""
        A = self.num_slots
        meta = np.zeros((self._meta_rows, A), np.int32)
        meta[0, :] = A                                  # pad -> scratch
        lasts = np.zeros((A,), np.int32)
        tables = np.zeros((A, self.max_pages), np.int32)
        from_waves: dict = {}     # id(firsts) -> (firsts, rows [A])
        n = 0
        now = time.monotonic()
        for pf, slot in installs:
            sl = pf.slot_state
            sl.installed_at = now
            self._slots[slot] = sl
            self._stale_slots.discard(slot)   # reuse doubles as redirect
            meta[0, n] = slot
            meta[1, n] = sl.pos
            meta[2, n] = int(sl.request.temperature * 1e6)
            if self._state_layers:
                meta[3, n] = sl.request.entry
            # what _length_reached will say on the host, one token out
            # (its first, fetched or not)
            meta[-2, n] = min(sl.request.max_new_tokens - 1,
                              self.cfg.max_seq_len - 1 - sl.pos)
            if sl.request.eos_id is not None:
                meta[-1, n] = sl.request.eos_id + 1
            if pf.source is None and pf.drafted is None:
                lasts[n] = sl.last_token
            else:
                # a drafting engine's install takes all it needs from
                # the wave's outputs, fetched or not
                firsts, row = pf.drafted or pf.source
                _, rows = from_waves.setdefault(
                    id(firsts), (firsts, np.full((A,), -1, np.int32)))
                rows[n] = row
            tables[n] = pf.table
            n += 1
        if all(s is None for s in self._slots):
            return None        # nothing to decode; redirects can wait
        for slot in sorted(self._stale_slots):
            if self._slots[slot] is None and n < A:
                meta[0, n] = slot   # zero token/pos/table -> scratch page
                n += 1
                self._stale_slots.discard(slot)
        if n:
            lasts = jnp.asarray(lasts)
            if self._drafts:    # a redirect row's draft and logits: zeros
                lasts = (lasts,) + self._no_admit[1][1:]
            for firsts, rows in from_waves.values():
                lasts = self._install_firsts_jit(lasts, firsts,
                                                 jnp.asarray(rows))
            admit = (jnp.asarray(meta), lasts, jnp.asarray(tables))
        else:
            admit = self._no_admit
        combined, self._state, self._cache = self._block_jit(
            self.params, self._cache, self._state, *admit)
        rows = [(i, s.request) for i, s in enumerate(self._slots)
                if s is not None]
        return (combined, rows)

    def _process_block(self, quantum, nxt_ahead: _Ahead) -> None:
        """Fetch and deliver one block (``quantum``: what
        ``_dispatch_block`` returned, and what was dispatched ahead of
        it); ``nxt_ahead`` is what was dispatched behind it, which the
        device turns to now."""
        combined, rows, ahead = quantum
        with self._phase("fetch_block", "fetch_wait_s") as sp:
            host = np.asarray(combined)    # the ONE fetch this quantum
            done = time.monotonic()
            # behind the tokens: the steps the block ran, and how many
            # of them drew (``_run_steps``)
            host, (steps_run, drawn, *load) = np.split(
                host, [-4 if self._counts_expert_load else -2])
            steps_run = int(steps_run)
            interval, wave_s = self._account_block(ahead, done, steps_run)
            self._begin(nxt_ahead, done)
            sp.set_metadata(block=self.stats.quanta + 1, rows=len(rows),
                            steps=steps_run, waves=ahead.waves,
                            interval_ms=round(1e3 * interval, 3),
                            wave_ms=round(1e3 * wave_s, 3))
        self.stats.block_steps_drawn += int(drawn)
        if load:
            self.stats.moe_layer_steps += int(load[0])
            self.stats.moe_experts_touched += int(load[1])
        # a drafting engine's block: [first | second | count]
        self._deliver_block(host.reshape(-1, self._rows, self.block_size)
                            if self._drafts
                            else host.reshape(self._rows, self.block_size),
                            rows, nxt_ahead, steps_run)
