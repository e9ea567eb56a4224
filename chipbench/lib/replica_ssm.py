"""``BenchLLMServer`` for a configuration whose layers are one mixer each:
state-space layers with a state entry a request, expert layers with a
router, attention layers with pages (``chipbench/README-ssm.md``).  The
base is ``lib/replica_hybrid.py HybridBenchLLMServer``: the serving path,
the trace, the facts, the one-program weights (``_load_params``) and the
warm-up that asks the engine how a prefill operand is packed
(``bench_warm``) are inherited untouched.  What differs is the check,
which has to know BOTH what ``replica_hybrid`` knows (a prefill is told
the prompt's real length and its state entry) and what ``replica_arch``
knows (the router's inputs and logits are captured in every expert
layer), and reads this model's own state leaves (``ssm_state``,
``ssm_conv``); ``replica_hybrid``'s check names ``gdn_state`` and
``gdn_decode`` and has no ``router_check``.

The check compares NUMBERS, on the chip, at the sizes the cell times.
``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, pool, state entries and page tables: the prompt by
the paged prefill path at the engine's bucket with its real length,
padded as the engine pads it; every later position as one decode step in
the engine's decode shape (``ssm_decode``, ``moe_experts_decode`` and
``paged_attention_decode`` and all), the request in row 0 on state entry
1.  It returns the hidden states, the router's inputs and logits in
every expert layer, and what the request's entry holds afterwards; the
reference module holds them to float32 and to references made wrong on
purpose.  ``paged_kernel_check`` then runs the paged decode kernel on
the pages that request left, against plain attention over the same keys,
and ``ssm_kernel_check`` the state-space decode kernel for 256 steps on
the states the window's requests left in the entries, against the
float32 recurrence, looking at the entries of the rows it was told are
dead (what ``gdn_kernel_check`` is to the other recurrent class: the
request's own state lies some percent from the reference's at the
published width, because its INPUTS are bfloat16, so it cannot show
whether the kernel's arithmetic keeps float32).
"""

import functools
import importlib
import re
import time

from chipbench.lib.replica_arch import served_token_agreement
from chipbench.lib.replica_hybrid import (DECODE_CHUNK, KERNEL_STEPS,
                                          HybridBenchLLMServer)


def _captured(mdl, method: str) -> bool:
    """The router's logits; its input the expert layer sows itself
    (``ops/moe.py LatentMoE``: ``router_in``, the one buffer its router,
    latent projection and shared expert read)."""
    return method == "router_logits"


def _router_io(intermediates):
    """``(z [L, B, T, d], r [L, B, T, E])`` of the expert layers from a
    captured tree (one period: the scanned axis has length 1)."""
    import jax
    import jax.numpy as jnp
    found = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        key = jax.tree_util.keystr(path)
        layer = re.search(r"layer_(\d+)", key)
        what = ("r" if "router_logits" in key
                else "z" if "router_in" in key else None)
        if layer and what:
            found.setdefault(int(layer.group(1)), {})[what] = leaf.reshape(
                (-1,) + leaf.shape[-3:])
    layers = sorted(found)
    return tuple(jnp.concatenate([found[j][k] for j in layers])
                 for k in ("z", "r"))


def _apply(model, params, cache, toks, poss, tabs, **recurrent):
    out, mut = model.apply(
        {"params": params, "cache": cache}, toks, poss, block_tables=tabs,
        return_hidden=True, mutable=["cache", "intermediates"],
        capture_intermediates=_captured, **recurrent)
    return out, _router_io(mut["intermediates"]), mut["cache"]


def program_hidden(eng, tokens, n_prompt: int) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool and
    state entries (pages 1.., entry 1 of an idle engine): positions ``<
    n_prompt`` in one paged prefill at the engine's bucket, told the real
    length; the others one decode step each, ``DECODE_CHUNK`` steps a
    call, the request in row 0 of the engine's rows.  ``hidden [S, d]``
    is post-final-norm, in the model's dtype; ``router_in [L, S, d]``,
    ``router_out [L, S, E]`` of the expert layers; ``state [L, N, H*P]``,
    ``tail`` what entry 1 holds after the last token; ``pages`` the
    request's."""
    import jax.numpy as jnp
    import numpy as np
    bucket = eng._bucket(n_prompt)
    table = np.zeros((1, eng.max_pages), np.int32)
    used = -(-len(tokens) // eng.page_size)
    table[0, :used] = 1 + np.arange(used)
    tables = np.zeros((eng._rows, eng.max_pages), np.int32)
    tables[0] = table[0]
    entries = np.zeros((eng._rows,), np.int32)
    entries[0] = 1

    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    eng._cache, (hid, io) = _prefill_of(eng, bucket)(
        eng.params, eng._cache, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray([n_prompt], jnp.int32))
    hid, io = [hid[:n_prompt]], [tuple(a[:, :n_prompt] for a in io)]
    # the last call runs on past the request's end (token 0, the
    # positions after it, whose pages are scratch): those rows are
    # dropped, and the state is read BEFORE them
    rest = np.asarray(tokens[n_prompt:], np.int32)
    poss = n_prompt + np.arange(len(rest), dtype=np.int32)
    for lo in range(0, len(rest), DECODE_CHUNK):
        real = min(DECODE_CHUNK, len(rest) - lo)
        toks, at = (np.zeros((DECODE_CHUNK,), np.int32) for _ in range(2))
        toks[:real], at[:real] = rest[lo:lo + real], poss[lo:lo + real]
        steps = np.zeros((DECODE_CHUNK,), bool)
        steps[:real] = True
        eng._cache, (more, more_io) = _decode_chunk_of(eng)(
            eng.params, eng._cache, jnp.asarray(toks), jnp.asarray(at),
            jnp.asarray(steps), jnp.asarray(tables), jnp.asarray(entries))
        hid.append(more[:real])
        io.append(tuple(jnp.moveaxis(a, 0, 1)[:, :real] for a in more_io))
    return {"hidden": jnp.concatenate(hid), "bucket": bucket,
            "router_in": jnp.concatenate([a[0] for a in io], 1),
            "router_out": jnp.concatenate([a[1] for a in io], 1),
            "state": eng._cache["ssm_state"][:, 1],
            "tail": eng._cache["ssm_conv"][:, 1], "pages": table[0, :used]}


def _prefill_of(eng, bucket: int):
    """``fn(params, cache, toks [1, bucket], table [1, pages], n [1]) ->
    (cache, (hidden [bucket, d], (z [L, bucket, d], r [L, bucket,
    E])))``: one prompt of real length ``n`` through the paged prefill
    path on state entry 1.  One jitted function an engine and bucket,
    the length and the table its operands, so that a second request of
    the bucket finds the first one's program."""
    import jax
    import jax.numpy as jnp
    made = eng.__dict__.setdefault("_bench_prefill", {})
    if bucket not in made:
        model = eng.model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, table, n):
            out, io, cache = _apply(
                model, params, cache, toks, jnp.arange(bucket)[None], table,
                lengths=n, state_rows=jnp.asarray([1], jnp.int32))
            return cache, (out[0], tuple(a[:, 0] for a in io))
        made[bucket] = fn
    return made[bucket]


def _decode_chunk_of(eng):
    """``fn(params, cache, toks [K], poss [K], steps [K], tables, entries)
    -> (cache, (hidden [K, d], (z [K, L, d], r [K, L, E])))``: one decode
    step a token in the engine's decode shape, the request in row 0; a
    step ``steps`` leaves out runs with every row dead (its tables
    zeroed), so it moves no state.  One jitted function an engine, so
    that a second request finds the first one's program."""
    import jax
    import jax.numpy as jnp
    fn = getattr(eng, "_bench_decode_chunk", None)
    if fn is None:
        model, n_rows = eng.model, eng._rows

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, poss, steps, tables, entries):
            rows = jnp.zeros((n_rows, 1), jnp.int32)

            def one(cache, xs):
                tok, pos, step = xs
                out, io, cache = _apply(
                    model, params, cache, rows.at[0, 0].set(tok),
                    rows.at[0, 0].set(pos), jnp.where(step, tables, 0),
                    state_rows=jnp.where(step, entries, 0))
                return cache, (out[0, 0], tuple(a[:, 0, 0] for a in io))
            return jax.lax.scan(one, cache, (toks, poss, steps))
        eng._bench_decode_chunk = fn
    return fn


def paged_kernel_check(eng, reference, pages, context: int) -> dict:
    """The paged decode kernel (32 query heads on 2 KV heads) on the
    pages a scored request of ``context`` positions left in the pool, in
    the engine's decode shape: every row reads the same pages, at lengths
    from one position to the whole context.  ``paged_kernel_rel_err`` is
    the largest row's |kernel - plain| / |plain| against
    ``reference.decode_attention`` over the same keys;
    ``paged_kernel_control`` the same against plain attention that misses
    each row's newest key (what a kernel one position short reads)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import paged_attention
    cfg = eng.cfg
    pool = next(a for a in jax.tree.leaves(eng._cache)
                if eng._is_pool_leaf(a))
    rows, hd = eng._rows, cfg.head_dim
    lengths = np.linspace(2, context, rows).astype(np.int32)
    tables = np.zeros((rows, eng.max_pages), np.int32)
    tables[:, :len(pages)] = pages
    q = jax.random.normal(jax.random.PRNGKey(0), (rows, cfg.n_heads, hd),
                          jnp.float32).astype(cfg.dtype)
    got = jax.jit(lambda pool: paged_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(lengths), layer=0))(
            pool).astype(jnp.float32)
    kv = pool[0, jnp.asarray(pages)]                # [n, KV, page, 2 hd]
    kv = jnp.moveaxis(kv, 1, 2).reshape(-1, kv.shape[1], 2 * hd)
    out = {}
    for name, lens in (("paged_kernel_rel_err", lengths),
                       ("paged_kernel_control", lengths - 1)):
        want = reference.decode_attention(q, kv[..., :hd], kv[..., hd:],
                                          jnp.asarray(lens))
        out[name] = float(jnp.max(
            jnp.linalg.norm((got - want).reshape(rows, -1), axis=-1)
            / jnp.linalg.norm(want.reshape(rows, -1), axis=-1)))
    return out


def ssm_kernel_check(eng, reference, steps: int = KERNEL_STEPS) -> dict:
    """``ops/mamba2.py ssm_decode`` as the engine's decode block calls it
    (its rows, its stacked state leaf, in place), ``steps`` steps in the
    last Mamba-2 layer (an index past 0), on the states the requests of the
    window left in the entries (row r on entry r + 1, the scratch row on
    scratch), two rows in three live, inputs over the model's own ranges.
    ``ssm_kernel_rel_err``: the live rows' outputs against
    ``reference.recurrence_check``'s float32 recurrence from the same
    states; its control: a recurrence that keeps its state in bfloat16.  ``dead_rows_untouched``: 1 where the entries of
    the rows that were not live are bit for bit what they were."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.mamba2 import ssm_decode
    cfg = eng.cfg
    rows, h, p = eng._rows, cfg.mamba_heads, cfg.mamba_head_dim
    g, n = cfg.mamba_groups, cfg.ssm_state_size
    entries = np.where(np.arange(rows) + 1 < eng.state_entries,
                       np.arange(rows) + 1, 0).astype(np.int32)
    live = (np.arange(rows) % 3 != 2) & (entries > 0)
    dead_entries = jnp.asarray(entries[~live & (entries > 0)])

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 5)
        return (jax.random.normal(ks[0], (steps, rows, h, p)
                                  ).astype(cfg.dtype),
                jnp.exp(jax.random.uniform(
                    ks[1], (steps, rows, h), minval=jnp.log(1e-3),
                    maxval=jnp.log(0.3))),
                -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0),
                jax.random.normal(ks[3], (steps, rows, g, n)
                                  ).astype(cfg.dtype),
                jax.random.normal(ks[4], (steps, rows, g, n)
                                  ).astype(cfg.dtype))
    u, delta, a_neg, b, c = inputs(jax.random.PRNGKey(0))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, layer, u, delta, a_neg, b, c):
        def one(state, xs):
            ut, dt, bt, ct = xs
            y, state = ssm_decode(
                dt[..., None] * ut.astype(jnp.float32),
                jnp.exp(dt * a_neg), bt, ct, state, jnp.asarray(entries),
                jnp.asarray(live), layer=layer)
            return state, y
        return jax.lax.scan(one, state, (u, delta, b, c))

    sel = jnp.asarray(np.flatnonzero(live))
    layer = cfg.layers_of("mamba2") - 1
    state = eng._cache["ssm_state"]
    before = state[layer]
    state0 = jax.vmap(lambda s: reference.from_program_state(
        s, jnp.zeros((cfg.mamba_conv_kernel - 1, 1)), h,
        cfg.mamba_conv_kernel)[0])(before[jnp.asarray(entries[live])])
    eng._cache["ssm_state"], y = run(state, jnp.int32(layer), u, delta,
                                     a_neg, b, c)
    untouched = bool(jnp.array_equal(
        eng._cache["ssm_state"][layer][dead_entries], before[dead_entries]))
    return {**reference.recurrence_check(
                y[:, sel], u[:, sel], delta[:, sel], a_neg, b[:, sel],
                c[:, sel], state0),
            "ssm_kernel_steps": steps, "ssm_kernel_live_rows": int(live.sum()),
            "dead_rows_untouched": int(untouched)}


def ssm_prefill_check(eng, reference, bucket: int = 256) -> dict:
    """``ops/mamba2.py ssm_chunked`` and the write of its final state
    into the stacked leaf, as a prefill wave of the engine calls them:
    two prompts of one bucket with real lengths (one a whole number of
    chunks less one token, one short), the configuration's chunk, the
    last Mamba-2 layer, entries 1 and 2 of the idle engine, inputs over
    the model's own ranges.  What the two entries hold afterwards
    against ``reference.prefill_state_check``: the float32 recurrence
    token by token over each prompt's REAL positions, and one that keeps
    its state in bfloat16 (a request's own state cannot tell them apart:
    its bfloat16 OPERANDS put it as far from the reference's, PERF.md
    section 6, PR 44)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops.mamba2 import ssm_chunked
    cfg = eng.cfg
    h, p = cfg.mamba_heads, cfg.mamba_head_dim
    g, n = cfg.mamba_groups, cfg.ssm_state_size
    layer = cfg.layers_of("mamba2") - 1
    entries = jnp.asarray([1, 2], jnp.int32)
    lengths = jnp.asarray([bucket - 1, bucket // 3], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    u = jax.random.normal(ks[0], (2, bucket, h, p)).astype(cfg.dtype)
    delta = jnp.exp(jax.random.uniform(
        ks[1], (2, bucket, h), minval=jnp.log(1e-3), maxval=jnp.log(0.3)))
    a_neg = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    b, c = (jax.random.normal(k, (2, bucket, g, n)).astype(cfg.dtype)
            for k in ks[3:])

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, u, delta, a_neg, b, c):
        _, final = ssm_chunked(u, delta, a_neg, b, c, lengths,
                               chunk=cfg.mamba_chunk)
        return gd.write_rows(state, gd.pack_state(final), layer, entries)

    eng._cache["ssm_state"] = run(eng._cache["ssm_state"], u, delta, a_neg,
                                  b, c)
    left = jax.vmap(lambda s: reference.from_program_state(
        s, jnp.zeros((cfg.mamba_conv_kernel - 1, 1)), h,
        cfg.mamba_conv_kernel)[0])(eng._cache["ssm_state"][layer, 1:3])
    return reference.prefill_state_check(left, u, delta, a_neg, b, c,
                                         lengths)


class SsmBenchLLMServer(HybridBenchLLMServer):

    def bench_reference(self, samples, config: dict) -> list:
        """Each sample's tokens (prompt, then what the engine streamed)
        through the engine's own model on the chip, against the module
        the configuration names, with the engine's own (served) weights:
        hidden states, the state and tail its entry was left with, the
        router's logits, the paged kernel on its pages; and once,
        with the last sample, the state-space decode kernel on the
        entries as the window left them.  The engine is idle: every
        request of the window has finished."""
        import jax.numpy as jnp
        reference = importlib.import_module(config["program"]["reference"])
        eng = self.engine
        cfg = eng.cfg
        took, t0 = {}, time.perf_counter()

        def lap(name):
            nonlocal t0
            now = time.perf_counter()
            took[name], t0 = round(now - t0, 2), now
        weights = reference.from_program_params(eng.params)
        # the kernel first: program_hidden overwrites entry 1
        kernel = ssm_kernel_check(eng, reference)
        kernel.update(ssm_prefill_check(eng, reference))
        lap("kernel")
        out = []
        # both requests at the longer one's length: the reference's
        # programs (one a layer kind) are compiled once, not once a
        # request (31 s of a first run; the shorter costs 7 s more)
        longest = max((len(s["prompt"]) + len(s["tokens"])
                       + eng._bucket(len(s["prompt"])) for s in samples),
                      default=0)
        for s, which in zip(samples, ("first", "second")):
            n = len(s["prompt"])
            seq = (list(s["prompt"]) + list(s["tokens"]))[:-1]
            got = program_hidden(eng, seq, n)
            m = {"context": n + len(s["tokens"]), "bucket": got["bucket"],
                 "served_token_agree_share": served_token_agreement(
                     eng, got["hidden"][n - 1:], s["tokens"])}
            lap(which + ".program")
            left = [reference.from_program_state(
                got["state"][layer], got["tail"][layer], cfg.mamba_heads,
                cfg.mamba_conv_kernel)
                for layer in range(cfg.layers_of("mamba2"))]
            m.update(reference.hidden_check(
                weights, jnp.asarray(seq), got["hidden"], config,
                n_prompt=n, bucket=got["bucket"], left=left,
                pad_to=longest))
            m.update(reference.router_check(weights, got["router_in"],
                                            got["router_out"]))
            lap(which + ".reference")
            m.update(paged_kernel_check(eng, reference, got["pages"],
                                        len(seq)))
            lap(which + ".paged")
            out.append(m)
        if out:
            out[-1].update(kernel, took_s=took)
        return out

    def device_info(self) -> dict:
        """``LLMServer.device_info`` and which implementations the
        state-space step and the experts' decode step resolve to here."""
        from ray_tpu.ops.mamba2 import resolve_ssm_impl
        from ray_tpu.ops.moe import expert_kernel_applies
        eng = self.engine
        cfg = eng.cfg
        # past HybridBenchLLMServer's, which asks after ``gdn_decode``
        info = super(HybridBenchLLMServer, self).device_info()
        return {**info,
                "ssm_impl": resolve_ssm_impl(
                    cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups),
                "moe_impl": "tpu" if expert_kernel_applies(
                    eng._rows * cfg.moe_top_k, cfg.moe_latent_size,
                    cfg.moe_d_ff, eng._rows) else "xla"}
