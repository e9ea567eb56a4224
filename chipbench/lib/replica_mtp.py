"""``BenchLLMServer`` for a configuration that is served DRAFTING with
its own multi-token-prediction module (``chipbench/README-mtp.md``): a
decode step verifies two positions a row and yields one or two tokens.
The serving path, the trace and the facts are inherited untouched; the
warm-up, the jitted parameter initialisation and the trace without the
Python call tracer are ``lib/replica_hybrid.py HybridBenchLLMServer``'s
(they ask the engine for its operand's width and take a prefill
program's result as it comes); the routers' capture is
``lib/replica_latent.py``'s (a router that reads the feed-forward's own
input).  The reference check is this file's, because a decode step here
is the engine's ``model_verify`` over TWO positions and the module's
forward behind it, and no other replica's check runs either.

The check compares NUMBERS, on the chip, at the sizes the cell times.
``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, pool and page tables: the prompt by the paged
prefill path at the engine's bucket and the module's forward over it,
as ``LLMEngine._first_draft`` runs it; every later position in the
engine's decode shape as the engine's step runs it: the stack over a
row's (token at p, token at p + 1) in one pass, the paged kernel at two
queries a row writing both K/V rows, then the module over the same two
positions with the tokens that follow them.  Teacher-forced: the served
tokens stand where a step's draft and its accepted tokens would, so each
position is computed once, odd ones as a step's first query and even
ones as its second.  It returns the stack's normed hidden states and
the module's at every position, and the routers' inputs and logits; the
reference module holds them to float32 and to references made wrong on
purpose.  ``verify_kernel_check`` then runs the paged kernel at two
queries a row on the pages that request left, a window layer, the global
layer and the module's, against plain attention over the same keys; and
``accept_check`` holds the acceptance probabilities the program's own
arithmetic gives for the served tokens to float32 and to a bfloat16
softmax.

Those run the model through programs this file owns.  What the TIMED
path did is held to the reference three ways (REVIEW.md, PR 46):
``block_replay`` steps rows of the sample, live together, with the
engine's own block program (``eng._block_jit``: verify, ``verify_draft``,
the choice of the module's row, the advance) and hands what it emitted
and the state it left to ``reference.replay_check``; ``hidden_check``
reads the tokens the window DELIVERED for the sample against the
reference's distributions of them (``served_loglik_z``) and says what
share of drafts has to stand over its positions, which the runner holds
the window's own counters to (``runners/serve_mtp.py``).
"""

import functools
import importlib
import time

from chipbench.lib.replica_hybrid import HybridBenchLLMServer
from chipbench.lib.replica_latent import _captured, _router_io

STEP_CHUNK = 64          # verify steps a call of ``_steps_of``'s program
ACCEPT_ROWS = 512        # positions the acceptance check reads
REPLAY_ROWS = 6          # rows live together in ``block_replay``
REPLAY_BLOCKS = 2        # calls of the engine's block program there


def _stack(model, params, cache, toks, poss, tabs):
    (out, pre), mut = model.apply(
        {"params": params, "cache": cache}, toks, poss, block_tables=tabs,
        return_hidden=True, return_prenorm=True,
        mutable=["cache", "intermediates"], capture_intermediates=_captured)
    return out, pre, _router_io(mut["intermediates"]), mut["cache"]


def _module(model, params, cache, nxt, poss, tabs, pre, **told):
    out, mut = model.apply(
        {"params": params, "cache": cache}, nxt, poss, block_tables=tabs,
        return_hidden=True, mtp_hidden=pre,
        mutable=["cache", "intermediates"], capture_intermediates=_captured,
        **told)
    return out, _router_io(mut["intermediates"]), mut["cache"]


def _join(io, io_mtp):
    """The stack's expert layers, then the module's: ``(z, r)``."""
    import jax.numpy as jnp
    return tuple(jnp.concatenate([a, b]) for a, b in zip(io, io_mtp))


def _prefill_of(eng, bucket: int):
    """``fn(params, cache, toks [1, bucket], nxt [1, bucket], table, n
    [1]) -> (cache, (hidden, mtp_hidden [bucket, d], (z [L, bucket, d], r
    [L, bucket, E])))``: one prompt through the paged prefill path and
    the module over it.  One jitted function an engine and bucket."""
    import jax
    import jax.numpy as jnp
    made = eng.__dict__.setdefault("_bench_prefill", {})
    if bucket not in made:
        model = eng.model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, nxt, table, n):
            poss = jnp.arange(bucket)[None]
            out, pre, io, cache = _stack(model, params, cache, toks, poss,
                                         table)
            drafted, io_mtp, cache = _module(model, params, cache, nxt, poss,
                                             table, pre, lengths=n)
            return cache, (out[0], drafted[0],
                           tuple(a[:, 0] for a in _join(io, io_mtp)))
        made[bucket] = fn
    return made[bucket]


def _steps_of(eng):
    """``fn(params, cache, toks [K, 2], nxt [K, 2], poss [K], tables) ->
    (cache, (hidden, mtp_hidden [K, 2, d], (z [K, L, 2, d], r [K, L, 2,
    E])))``: one verify step a pair of tokens in the engine's decode
    shape (``model_verify``), the request in row 0, the module behind the
    stack as ``LLMEngine._spec_block_fn`` runs it.  One jitted function
    an engine."""
    import jax
    import jax.numpy as jnp
    fn = getattr(eng, "_bench_steps", None)
    if fn is None:
        model, n_rows = eng.model_verify, eng._rows

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, nxt, poss, tables):
            rows = jnp.zeros((n_rows, 2), jnp.int32)

            def one(cache, xs):
                tok, nx, pos = xs
                at = rows.at[0].set(jnp.stack([pos, pos + 1]))
                out, pre, io, cache = _stack(model, params, cache,
                                             rows.at[0].set(tok), at, tables)
                drafted, io_mtp, cache = _module(
                    model, params, cache, rows.at[0].set(nx), at, tables,
                    pre)
                return cache, (out[0], drafted[0], tuple(
                    a[:, 0] for a in _join(io, io_mtp)))
            return jax.lax.scan(one, cache, (toks, nxt, poss))
        eng._bench_steps = fn
    return fn


def program_hidden(eng, tokens, n_prompt: int, last_next: int = 0) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool
    (pages 1.. of an idle engine): positions ``< n_prompt`` in one paged
    prefill at the engine's bucket and the module over them; the others
    two a verify step, the request in row 0 of the engine's rows,
    ``STEP_CHUNK`` steps a call (the last call runs on past the end at
    token 0, whose rows are dropped).  ``hidden``, ``mtp_hidden [S, d]``
    are the stack's and the module's normed outputs (the module's entry
    i read token i + 1, ``last_next`` behind the last), in the model's
    dtype; ``router_in [L, S, d]``, ``router_out [L, S, E]`` of the
    stack's expert layers, then the module's; ``pages`` the request's."""
    import jax.numpy as jnp
    import numpy as np
    s = len(tokens)
    bucket = eng._bucket(n_prompt)
    steps = -(-(s - n_prompt) // 2)
    padded = -(-steps // STEP_CHUNK) * STEP_CHUNK
    nxt_all = np.zeros((n_prompt + 2 * padded + 1,), np.int32)
    nxt_all[:s - 1], nxt_all[s - 1] = tokens[1:], last_next
    toks_all = np.zeros_like(nxt_all)
    toks_all[:s] = tokens
    used = min(-(-(n_prompt + 2 * padded) // eng.page_size), eng.max_pages)
    table = np.zeros((1, eng.max_pages), np.int32)
    table[0, :used] = 1 + np.arange(used)
    tables = np.zeros((eng._rows, eng.max_pages), np.int32)
    tables[0] = table[0]

    prompt, nxt = (np.zeros((1, bucket), np.int32) for _ in range(2))
    prompt[0, :n_prompt] = tokens[:n_prompt]
    nxt[0, :n_prompt] = nxt_all[:n_prompt]
    eng._cache, (hid, mtp, io) = _prefill_of(eng, bucket)(
        eng.params, eng._cache, jnp.asarray(prompt), jnp.asarray(nxt),
        jnp.asarray(table), jnp.asarray([n_prompt], jnp.int32))
    hid, mtp = [hid[:n_prompt]], [mtp[:n_prompt]]
    io = [tuple(a[:, :n_prompt] for a in io)]
    # steps past the end stop one short of the last position, as the
    # engine's do
    poss = np.minimum(n_prompt + 2 * np.arange(padded),
                      eng.cfg.max_seq_len - 2).astype(np.int32)
    pairs = lambda a: a[n_prompt:n_prompt + 2 * padded].reshape(-1, 2)  # noqa: E731
    for lo in range(0, padded, STEP_CHUNK):
        cut = slice(lo, lo + STEP_CHUNK)
        eng._cache, (more, more_mtp, io2) = _steps_of(eng)(
            eng.params, eng._cache, jnp.asarray(pairs(toks_all)[cut]),
            jnp.asarray(pairs(nxt_all)[cut]), jnp.asarray(poss[cut]),
            jnp.asarray(tables))
        hid.append(more.reshape(-1, more.shape[-1]))
        mtp.append(more_mtp.reshape(-1, more.shape[-1]))
        # [K, L, 2, x] -> [L, K * 2, x]
        io.append(tuple(jnp.moveaxis(a, 0, 1).reshape(
            a.shape[1], -1, a.shape[-1]) for a in io2))
    return {"hidden": jnp.concatenate(hid)[:s],
            "mtp_hidden": jnp.concatenate(mtp)[:s], "bucket": bucket,
            "router_in": jnp.concatenate([p[0] for p in io], 1)[:, :s],
            "router_out": jnp.concatenate([p[1] for p in io], 1)[:, :s],
            "pages": table[0, :-(-s // eng.page_size)], "pages_used": used}


def verify_kernel_check(eng, reference, pages, context: int) -> dict:
    """The paged decode kernel at TWO queries a row on the pages a
    scored request of ``context`` positions left in the pool, in the
    engine's decode shape: every row reads the same pages, at lengths
    from two positions to the whole context, one row in four dead; in a
    window layer, the global layer and the module's.  The two new rows
    handed to it are the ones the pages hold already, so the pool comes
    back as it went in.  ``verify_kernel_rel_err`` is the largest live
    row's |kernel - plain| / |plain| against ``reference.verify_attention``
    over the same keys in float32; ``verify_kernel_control`` the same
    against attention whose two queries both see the row's newest key
    (a kernel that is not causal between a step's positions).
    ``verify_kernel_dead_rows_zero``: 1 where every dead row came back
    zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import paged_attention
    cfg = eng.cfg
    rows, hd, ps = eng._rows, cfg.head_dim, eng.page_size
    lengths = np.linspace(2, context, rows).astype(np.int32)
    live = np.arange(rows) % 4 != 3
    sel = np.flatnonzero(live)
    tables = np.zeros((rows, eng.max_pages), np.int32)
    tables[:, :len(pages)] = pages
    q = jax.random.normal(jax.random.PRNGKey(0), (rows, 2, cfg.n_heads, hd),
                          jnp.float32).astype(cfg.dtype)

    @functools.partial(jax.jit, donate_argnums=(0,),
                       static_argnames=("window",))
    def kernel(pool, layer, new, window=None):
        return paged_attention(q, pool, jnp.asarray(tables),
                               jnp.asarray(lengths), layer=layer,
                               new_rows=new, live=jnp.asarray(live),
                               window=window)

    sliding = [bool(v) for v in cfg.window_layout[:cfg.n_layers]]
    layers = {"window": sliding.index(True), "global": sliding.index(False),
              "module": cfg.n_layers}
    worst = control = 0.0
    zero = True
    for name, layer in layers.items():
        window = cfg.sliding_window if name == "window" else None
        pool = eng._cache["kv_pages"]
        kv = jnp.moveaxis(pool[layer, jnp.asarray(pages)], 1, 2).reshape(
            -1, pool.shape[2], 2 * hd)          # [positions, KV, 2 hd]
        at = np.stack([lengths - 2, lengths - 1], 1)
        got, eng._cache["kv_pages"] = kernel(
            pool, jnp.int32(layer), kv[jnp.asarray(at)], window=window)
        got = got.astype(jnp.float32)
        zero &= not bool(jnp.any(got[np.flatnonzero(~live)] != 0))
        want = reference.verify_attention(
            q[sel], kv[..., :hd], kv[..., hd:], jnp.asarray(lengths[sel]),
            window=window)
        both = reference.verify_attention(
            q[sel].reshape(-1, 1, cfg.n_heads, hd), kv[..., :hd],
            kv[..., hd:], jnp.asarray(np.repeat(lengths[sel], 2)),
            window=window).reshape(want.shape)
        err = lambda a: float(jnp.max(                       # noqa: E731
            jnp.linalg.norm((got[sel] - a).reshape(len(sel), -1), axis=-1)
            / jnp.linalg.norm(a.reshape(len(sel), -1), axis=-1)))
        # np.maximum, not max(): a NaN reading has to come out NaN
        worst = float(np.maximum(worst, err(want)))
        control = float(np.maximum(control, err(both)))
    return {"verify_kernel_rel_err": worst, "verify_kernel_control": control,
            "verify_kernel_dead_rows_zero": int(zero),
            "verify_kernel_live_rows": int(live.sum())}


def accept_check(eng, reference, got, seq, temperature: float) -> dict:
    """The acceptance probability of each of the last ``ACCEPT_ROWS``
    served tokens taken as a draft, as the engine's step computes it
    (``models/generate.py sampling_probs`` and the ratio of
    ``accept_draft`` on the program's own logits: the stack's at i, the
    module's at i - 1, both of token i + 1), against
    ``reference.accept_check``."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.generate import sampling_probs
    from ray_tpu.models.gpt import output_logits
    lo = max(1, len(seq) - 1 - ACCEPT_ROWS)
    drafts = jnp.asarray(seq[lo + 1:])
    temps = jnp.full(drafts.shape, temperature, jnp.float32)

    @jax.jit
    def mine(params, main, module):
        l1 = output_logits(eng.cfg, params, main)
        lq = output_logits(eng.cfg, params, module)
        p1, q = (sampling_probs(lg, temps, top_k=eng.top_k, top_p=eng.top_p)
                 for lg in (l1, lq))
        at = drafts[:, None]
        return l1, lq, jnp.minimum(1.0, jnp.take_along_axis(p1, at, 1)[:, 0]
                                   / jnp.take_along_axis(q, at, 1)[:, 0])
    l1, lq, prob = mine(eng.params, got["hidden"][lo:],
                        got["mtp_hidden"][lo - 1:-1])
    return reference.accept_check(l1, lq, drafts, prob, temperature)


def block_replay(eng, seq, got, n_prompt: int, temperature: float,
                 longest: int, seed: int) -> dict:
    """The engine's OWN block program (``eng._block_jit``:
    ``engine_decode_block``, the one the window timed) on rows that
    continue the request ``seq`` from several of its positions at once.
    ``program_hidden`` has left the request's K/V rows in the pool, the
    stack's and the module's.  Up to ``REPLAY_ROWS`` slots, spread over
    the engine's rows with dead rows between them, are installed as
    ``_dispatch_block`` installs a request: each at a cut ``c`` on a
    page's edge, from the prompt's end to the request's, its table the
    request's pages below ``c`` (read by every row, written by none) and
    pages of its own from ``c`` on, its last token ``seq[c]``, its draft
    drawn from the module's logits at ``c - 1`` as the program computed
    them.  Then ``REPLAY_BLOCKS`` calls: verify, accept, advance, draft,
    ``block_size`` times each, from a fresh state seeded ``seed``.  What
    comes back is the block's one fetch and the state it leaves.  A row
    of the result is ``reference.replay_check``'s; ``replay_state_ok``
    is 1 where after every call each row's position in the state is its
    cut plus what it emitted, its token the last it emitted, every count
    1 or 2 and every dead row still at position 0.  ``longest``: no row
    may grow past it (the reference's compiled length)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.gpt import output_logits
    ps, k, slots_n = eng.page_size, eng.block_size, eng.num_slots
    room = 2 * REPLAY_BLOCKS * k + 2        # positions a row may reach
    tail = -(-room // ps) + 1               # pages of its own
    hi = min(len(seq) - 1, eng.cfg.max_seq_len - room, longest - room)
    hi -= hi % ps
    n = min(REPLAY_ROWS, slots_n,
            (eng.kv_pool_pages - 1 - got["pages_used"]) // tail)
    if hi < ps or n < 1 or temperature <= 0:
        return {}
    lo = min(-(-n_prompt // ps) * ps, hi)
    cuts = (np.linspace(lo, hi, n) // ps * ps).astype(np.int32)
    slots = np.unique(np.linspace(0, slots_n - 1, n).astype(np.int32))
    cuts, n = cuts[:len(slots)], len(slots)

    meta = np.asarray(eng._no_admit[0]).copy()
    meta[0, :n], meta[1, :n] = slots, cuts
    meta[2, :n] = int(temperature * 1e6)
    tables = np.zeros((slots_n, eng.max_pages), np.int32)
    for r, c in enumerate(cuts):
        own = 1 + got["pages_used"] + r * tail + np.arange(tail)
        row = np.concatenate([got["pages"][:c // ps], own])[:eng.max_pages]
        tables[r, :len(row)] = row
    key = jax.random.PRNGKey(seed)
    q = output_logits(eng.cfg, eng.params, got["mtp_hidden"][cuts - 1])
    drafts = eng._sample_fn(key, q, jnp.full((n,), temperature, jnp.float32))
    pad = lambda a: jnp.concatenate(                          # noqa: E731
        [a, jnp.zeros((slots_n - n, *a.shape[1:]), a.dtype)])
    admit = (jnp.asarray(meta), (
        pad(jnp.asarray(np.asarray(seq, np.int32)[cuts])),
        pad(drafts.astype(jnp.int32)), pad(q.astype(jnp.float32))),
        jnp.asarray(tables))
    state = eng._init_state(seed)
    rows = [{"start": int(c), "tokens": [int(t) for t in seq[:c + 1]],
             "counts": [], "q_logits": []} for c in cuts]
    dead = np.setdiff1d(np.arange(eng._rows), slots)
    ok = True
    for _ in range(REPLAY_BLOCKS):
        combined, state, eng._cache = eng._block_jit(
            eng.params, eng._cache, state, *admit)
        admit = eng._no_admit
        host = np.asarray(combined)[:3 * eng._rows * k]
        first, second, count = host.reshape(3, eng._rows, k)
        at, last = np.asarray(state[1]), np.asarray(state[0])
        for r, slot in enumerate(slots):
            row = rows[r]
            for a, b, c in zip(first[slot], second[slot], count[slot]):
                row["tokens"] += [int(a), int(b)][:int(c)]
                row["counts"].append(int(c))
            row["q_logits"].append((len(row["counts"]),
                                    np.asarray(state[-1][slot])))
            ok &= bool(set(row["counts"]) <= {1, 2}
                       and at[slot] == row["start"] + sum(row["counts"])
                       and last[slot] == row["tokens"][-1])
        ok &= not at[dead].any()
    return {"rows": rows, "replay_state_ok": int(ok),
            "replay_cuts": [int(c) for c in cuts]}


class MtpBenchLLMServer(HybridBenchLLMServer):

    def __init__(self, *args, temperature: float = 0.0, **kwargs):
        """``temperature``: what a request that names none is sampled
        at (the mix's ``server.temperature``: ``runners/serve.py _one``,
        which every serve runner drives its requests with, sends a
        prompt and ``max_new_tokens`` and nothing else)."""
        super().__init__(*args, **kwargs)
        self._temperature = float(temperature)

    async def stream(self, request):
        async for item in super().stream(
                {"temperature": self._temperature, **request}):
            yield item

    def bench_reference(self, samples, config: dict) -> list:
        """Each sample's tokens (prompt, then what the engine streamed)
        through the engine's own model on the chip, against the module
        the configuration names, with the engine's own (served) weights;
        the kernel at two queries a row on the pages that request left;
        the acceptance arithmetic on its logits; the engine's own block
        program on rows that continue the sample (``block_replay``);
        the tokens the window delivered against the reference's
        distributions.  The engine is idle: every request of the window
        has finished."""
        import jax.numpy as jnp
        reference = importlib.import_module(config["program"]["reference"])
        eng = self.engine
        took, t0 = {}, time.perf_counter()

        def lap(name):
            nonlocal t0
            now = time.perf_counter()
            took[name], t0 = round(now - t0, 2), now
        weights = reference.from_program_params(eng.params)
        longest = max((len(s["prompt"]) + len(s["tokens"]) for s in samples),
                      default=0)
        # the length the reference compiles its layers at
        padded = longest + -longest % getattr(reference, "PAD", 1)
        out = []
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            seq = list(s["prompt"]) + list(s["tokens"])
            # the last served token is read by the module alone
            seq, last = seq[:-1], seq[-1]
            got = program_hidden(eng, seq, n, last_next=last)
            m = {"context": len(seq) + 1, "bucket": got["bucket"]}
            lap(f"{i}.program")
            m.update(verify_kernel_check(eng, reference, got["pages"],
                                         len(seq)))
            m.update(accept_check(eng, reference, got, seq + [last],
                                  self._temperature))
            replay = block_replay(eng, seq, got, n, self._temperature,
                                  padded, seed=i)
            lap(f"{i}.kernel")
            m.update(reference.router_check(
                weights, got["router_in"], got["router_out"]))
            m.update(reference.hidden_check(
                weights, jnp.asarray(seq), got["hidden"], got["mtp_hidden"],
                config, faults=s.get("faults", reference.FAULTS),
                pad_to=longest, last_next=last, served_from=n,
                temperature=self._temperature))
            lap(f"{i}.reference")
            if replay:
                m.update(reference.replay_check(
                    weights, config, replay.pop("rows"), self._temperature,
                    pad_to=longest), **replay)
                lap(f"{i}.replay")
            out.append(m)
        if out:
            out[-1]["took_s"] = took
        return out

    def device_info(self) -> dict:
        """``LLMServer.device_info`` and which implementation the expert
        layers' step resolves to here (two positions a row)."""
        from ray_tpu.ops.moe import expert_kernel_applies
        from ray_tpu.serve.llm import LLMServer
        eng = self.engine
        cfg = eng.cfg
        return {**LLMServer.device_info(self),
                "moe_impl": "tpu" if expert_kernel_applies(
                    2 * eng._rows * cfg.moe_top_k, cfg.d_model, cfg.moe_d_ff,
                    2 * eng._rows) else "xla"}
