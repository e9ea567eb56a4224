"""``BenchLLMServer`` for a configuration whose recurrent layers are Kimi
Delta Attention, whose attention layers keep a LATENT row and do not
rotate, and whose layers route to experts of which this chip holds a
share (``chipbench/README-kda.md``).  The base is ``lib/replica_hybrid.py
HybridBenchLLMServer``: the serving path, the trace, the facts, the
one-program weights and the warm-up are inherited untouched (they ask
the engine for its operand's width).  The reference check is this
file's: ``replica_hybrid``'s knows a scalar decay and no experts,
``replica_latent``'s no state entries and a ``dense_blocks`` / ``blocks``
tree, ``replica_ssm_dense``'s another recurrence.

The check compares NUMBERS, on the chip, at the sizes the cell times.
``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, latent pool, state entries and page tables: the
prompt by the paged prefill path at the engine's bucket with its real
length; every later position as one decode step in the engine's decode
shape (``kda_decode``, the absorbed latent kernel, ``moe_experts_decode``
and all), the request in row 0 on state entry 1.  It returns the hidden
states, the routers' inputs and logits, and what entry 1 holds in every
KDA layer after the prompt and after the last step.  The reference
module holds them to float32 and to references made wrong on purpose.
``kda_kernel_check`` runs the decode kernel on the states the window
left in the entries and ``kda_chunk_check`` the chunked prompt form on
given inputs whose decays reach both ends of the published range, each
against the token-by-token recurrence; ``latent_kernel_check`` the
absorbed decode on the pages the request left in each of the pool's
layers; ``engine_path`` puts a prompt through the engine's OWN compiled
programs, the ones the window timed, and reads what they left behind.
"""

import functools
import importlib
import time

from chipbench.lib.replica_arch import served_token_agreement
from chipbench.lib.replica_hybrid import DECODE_CHUNK, HybridBenchLLMServer
from chipbench.lib.replica_ssm_dense import program_logits

KERNEL_STEPS = 128
CHUNK_ROWS, CHUNK_TOKENS = 4, 1024      # two segments of eight chunks a row


def _captured(mdl, method: str) -> bool:
    """The routers' inputs (every block's ``mlp_norm``) and logits."""
    return method == "router_logits" or (
        method == "__call__" and mdl.name == "mlp_norm")


def _router_io(intermediates, places):
    """``(z [L, B, T, d], r [L, B, T, E])`` of the expert layers IN LAYER
    ORDER from a captured tree; ``places``: ``[(run, layer name, index
    in the scan or None)]`` of every layer (the reference's
    ``weights["layers"]``); a layer without a router is left out."""
    import jax
    import jax.numpy as jnp
    found = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        keys = [getattr(k, "key", None) for k in path]
        what = ("r" if "router_logits" in keys else
                "z" if "mlp_norm" in keys else None)
        if what:
            found[(keys[0], keys[1], what)] = leaf
    z, r = [], []
    for run, name, i in places:
        if (run, name, "r") not in found:
            continue
        for what, into in (("z", z), ("r", r)):
            leaf = found[(run, name, what)]
            into.append(leaf if i is None else leaf[i])
    return jnp.stack(z), jnp.stack(r)


def _apply(model, params, cache, toks, poss, tabs, places, **recurrent):
    out, mut = model.apply(
        {"params": params, "cache": cache}, toks, poss, block_tables=tabs,
        return_hidden=True, mutable=["cache", "intermediates"],
        capture_intermediates=_captured, **recurrent)
    return out, _router_io(mut["intermediates"], places), mut["cache"]


def _prefill_of(eng, bucket: int, places):
    """``fn(params, cache, toks [1, bucket], table, n [1]) -> (cache,
    (hidden [bucket, d], (z [L, bucket, d], r [L, bucket, E])))``: one
    prompt of real length ``n`` through the paged prefill path on state
    entry 1.  One jitted function an engine and bucket."""
    import jax
    import jax.numpy as jnp
    made = eng.__dict__.setdefault("_bench_prefill", {})
    if bucket not in made:
        model = eng.model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, table, n):
            out, io, cache = _apply(
                model, params, cache, toks, jnp.arange(bucket)[None], table,
                places, lengths=n, state_rows=jnp.asarray([1], jnp.int32))
            return cache, (out[0], tuple(a[:, 0] for a in io))
        made[bucket] = fn
    return made[bucket]


def _decode_chunk_of(eng, places):
    """``fn(params, cache, toks [K], poss [K], steps [K], tables, entries)
    -> (cache, (hidden [K, d], (z [K, L, d], r [K, L, E])))``: one decode
    step a token in the engine's decode shape, the request in row 0; a
    step ``steps`` leaves out runs with every row dead (its tables
    zeroed), so it moves no state."""
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
                    places, state_rows=jnp.where(step, entries, 0))
                return cache, (out[0, 0], tuple(a[:, 0, 0] for a in io))
            return jax.lax.scan(one, cache, (toks, poss, steps))
        eng._bench_decode_chunk = fn
    return fn


def _entry_one(eng):
    """What state entry 1 holds in every KDA layer, as copies."""
    import jax.numpy as jnp
    return (jnp.array(eng._cache["gdn_state"][:, 1]),
            jnp.array(eng._cache["gdn_conv"][:, 1]))


def program_hidden(eng, tokens, n_prompt: int, places) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool and
    state entries (pages 1.., entry 1 of an idle engine): positions ``<
    n_prompt`` in one paged prefill at the engine's bucket, told the real
    length; the others one decode step each, ``DECODE_CHUNK`` steps a
    call, the request in row 0 of the engine's rows.  ``hidden [S, d]``
    is post-final-norm, in the model's dtype; ``router_in [L, S, d]``,
    ``router_out [L, S, E]`` of the expert layers; ``left``: entry 1's
    ``(state [K, dk, H*dv], tail)`` after the prompt and after the last
    token; ``pages`` the request's."""
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
    eng._cache, (hid, io) = _prefill_of(eng, bucket, places)(
        eng.params, eng._cache, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray([n_prompt], jnp.int32))
    left = {"prompt": _entry_one(eng)}
    hid, zs, rs = [hid[:n_prompt]], [io[0][:, :n_prompt]], \
        [io[1][:, :n_prompt]]
    rest = np.asarray(tokens[n_prompt:], np.int32)
    poss = n_prompt + np.arange(len(rest), dtype=np.int32)
    step = _decode_chunk_of(eng, places)
    for lo in range(0, len(rest), DECODE_CHUNK):
        real = min(DECODE_CHUNK, len(rest) - lo)
        toks, at = (np.zeros((DECODE_CHUNK,), np.int32) for _ in range(2))
        toks[:real], at[:real] = rest[lo:lo + real], poss[lo:lo + real]
        steps = np.zeros((DECODE_CHUNK,), bool)
        steps[:real] = True
        eng._cache, (more, io) = step(
            eng.params, eng._cache, jnp.asarray(toks), jnp.asarray(at),
            jnp.asarray(steps), jnp.asarray(tables), jnp.asarray(entries))
        hid.append(more[:real])
        zs.append(jnp.moveaxis(io[0], 0, 1)[:, :real])
        rs.append(jnp.moveaxis(io[1], 0, 1)[:, :real])
    left["end"] = _entry_one(eng)
    return {"hidden": jnp.concatenate(hid), "bucket": bucket, "left": left,
            "router_in": jnp.concatenate(zs, 1),
            "router_out": jnp.concatenate(rs, 1), "pages": table[0, :used]}


def _kda_inputs(cfg, key, lead: tuple):
    """``(q, k, v, g, beta)`` of shapes ``lead + [H, ..]`` over the
    model's own ranges: unit keys, scaled unit queries, write strengths
    in (0, 1), log decays log-uniform from -3e-4 to -11 a step (both
    ends of what the published initial ranges give).  Made on the
    device, as arguments of what runs them, not constants of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    h = cfg.linear_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    ks = jax.random.split(key, 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return ((unit(jax.random.normal(ks[0], lead + (h, dk)))
             * dk ** -0.5).astype(cfg.dtype),
            unit(jax.random.normal(ks[1], lead + (h, dk))).astype(cfg.dtype),
            jax.random.normal(ks[2], lead + (h, dv)).astype(cfg.dtype),
            -jnp.exp(jax.random.uniform(
                ks[3], lead + (h, dk), minval=np.log(3e-4),
                maxval=np.log(11.0))),
            jax.nn.sigmoid(jax.random.normal(ks[4], lead + (h,))))


def kda_kernel_check(eng, reference, steps: int = KERNEL_STEPS) -> dict:
    """``ops/gated_delta.py gdn_decode`` under a decay a channel as the
    engine's decode block calls it (its rows, its stacked state leaf, in
    place), ``steps`` steps in the first, the middle and the last KDA
    layer (one kernel, its layer an operand: these hold its indexing),
    on the states the requests of the window left in the entries (row r
    on entry r + 1), two rows in three live.  ``kda_kernel_rel_err``: the live rows'
    outputs against ``reference.recurrence_check``'s float32 recurrence
    from the same states, worst layer; its controls: a recurrence that
    keeps its state in bfloat16, and one that decays every channel of a
    head by their mean (Gated DeltaNet's rule).  ``dead_rows_untouched``:
    1 where the entries of the rows that were not live are bit for bit
    what they were.  ``kda_step_us``: the kernel's wall time a layer
    step here (for the log)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.gated_delta import gdn_decode, unpack_state
    cfg = eng.cfg
    rows, h = eng._rows, cfg.linear_value_heads
    entries = np.where(np.arange(rows) + 1 < eng.state_entries,
                       np.arange(rows) + 1, 0).astype(np.int32)
    live = (np.arange(rows) % 3 != 2) & (entries > 0)
    dead_entries = jnp.asarray(entries[~live & (entries > 0)])
    xs = jax.jit(lambda key: _kda_inputs(cfg, key, (steps, rows)))(
        jax.random.PRNGKey(0))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, layer, *xs):
        def one(state, x):
            o, state = gdn_decode(*x, state, jnp.asarray(entries),
                                  jnp.asarray(live), layer=layer)
            return state, o
        return jax.lax.scan(one, state, xs)

    sel = jnp.asarray(np.flatnonzero(live))
    out = {"kda_kernel_rel_err": 0.0,
           "kda_kernel_bf16_state_control": float("inf"),
           "kda_kernel_scalar_decay_control": float("inf")}
    untouched, took = True, []
    last = eng._state_layers - 1
    for layer in sorted({0, last // 2, last}):
        state = eng._cache["gdn_state"]
        before = state[layer]
        state0 = unpack_state(before[jnp.asarray(entries[live])], h)
        t0 = time.perf_counter()
        state, o = run(state, jnp.int32(layer), *xs)
        o.block_until_ready()
        took.append(time.perf_counter() - t0)
        eng._cache["gdn_state"] = state
        untouched &= bool(jnp.array_equal(state[layer][dead_entries],
                                          before[dead_entries]))
        m = reference.recurrence_check(o[:, sel], *(a[:, sel] for a in xs),
                                       state0)
        # np.maximum / minimum, not max(): a NaN has to come out as NaN
        out["kda_kernel_rel_err"] = float(np.maximum(
            out["kda_kernel_rel_err"], m["rel_err"]))
        for name in ("bf16_state", "scalar_decay"):
            key = f"kda_kernel_{name}_control"
            out[key] = float(np.minimum(out[key], m[name + "_control"]))
    return {**out, "kda_kernel_steps": steps,
            "kda_kernel_live_rows": int(live.sum()),
            "dead_rows_untouched": int(untouched),
            "kda_step_us": 1e6 * min(took[1:] or took) / steps}


def kda_chunk_check(eng, reference) -> dict:
    """``ops/gated_delta.py gated_delta_chunked`` under a decay a
    channel, at the published widths, on ``CHUNK_ROWS`` prompts of
    ``CHUNK_TOKENS`` tokens (more than one segment of the form's outer
    scan) whose decays reach both ends of the published range (where a
    factored chunk form leaves float32), two of them shorter than the
    batch: outputs at the real positions and
    the final states against the token-by-token recurrence.
    ``kda_chunk_rel_err`` the worst row's; the controls as
    ``kda_kernel_check``'s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.gated_delta import gated_delta_chunked
    cfg = eng.cfg
    xs = jax.jit(lambda key: _kda_inputs(
        cfg, key, (CHUNK_ROWS, CHUNK_TOKENS)))(jax.random.PRNGKey(1))
    lengths = np.full((CHUNK_ROWS,), CHUNK_TOKENS, np.int32)
    lengths[1], lengths[2] = CHUNK_TOKENS - 37, CHUNK_TOKENS // 2 + 1
    o, state = jax.jit(gated_delta_chunked)(*xs, jnp.asarray(lengths))
    real = jnp.arange(CHUNK_TOKENS)[None, :] < jnp.asarray(lengths)[:, None]
    q, k, v, g, beta = xs
    # the reference takes [T, R, ..]; past a row's length nothing moves
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    o = jnp.where(real[..., None, None], o, 0.0)
    t_major = lambda a: jnp.moveaxis(a, 0, 1)                # noqa: E731
    m = reference.recurrence_check(
        t_major(o), t_major(q), t_major(k), t_major(v), t_major(g),
        t_major(beta), jnp.zeros_like(state), mask=t_major(real))
    return {"kda_chunk_rel_err": m["rel_err"],
            "kda_chunk_bf16_state_control": m["bf16_state_control"],
            "kda_chunk_scalar_decay_control": m["scalar_decay_control"],
            "kda_chunk_state_finite": int(bool(jnp.isfinite(state).all()))}


def latent_kernel_check(eng, reference, weights, pages, context: int) -> dict:
    """The absorbed decode on the pages a scored request of ``context``
    positions left in the pool, in the engine's decode shape: every row
    reads the same pages, at lengths from one position to the whole
    context, one row in four dead, in every layer OF THE POOL (the
    latent layers alone).  ``latent_kernel_rel_err`` is the largest live
    row's |program - plain| / |plain| against
    ``reference.decode_attention``: EXPANDED attention over the same rows
    in float32 with the layer's own ``wkv_b``; ``latent_kernel_control``
    the same against that attention with the softmax scale of the first
    128 dims alone.  ``latent_kernel_dead_rows_zero``: 1 where every
    dead row came back zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.gpt import absorbed_attention
    cfg = eng.cfg
    pool = eng._cache["kv_pages"]
    rows, r, dn = eng._rows, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    lengths = np.linspace(1, context, rows).astype(np.int32)
    live = np.arange(rows) % 4 != 3
    tables = np.zeros((rows, eng.max_pages), np.int32)
    tables[:, :len(pages)] = pages
    q = jax.random.normal(jax.random.PRNGKey(0),
                          (rows, cfg.n_heads, cfg.head_dim), jnp.float32
                          ).astype(cfg.dtype)
    kernel = jax.jit(lambda pool, wkv_b, layer: absorbed_attention(
        cfg, q, wkv_b, pool, jnp.asarray(tables), jnp.asarray(lengths),
        layer=layer, live=jnp.asarray(live)))
    latent = [i for i, kind in enumerate(cfg.layer_types[:cfg.n_layers])
              if kind == "full_attention"]
    assert len(latent) == pool.shape[0]
    worst, control, zero = 0.0, float("inf"), True
    sel = np.flatnonzero(live)
    for layer, index in enumerate(latent):
        wkv_b = reference.latent_up_projection(weights, index)
        got = kernel(pool, wkv_b, jnp.int32(layer)).astype(jnp.float32)
        zero &= not bool(jnp.any(got[np.flatnonzero(~live)] != 0))
        cached = pool[layer, jnp.asarray(pages), 0].reshape(
            -1, pool.shape[-1])
        for scale_dim in (None, dn):
            want = reference.decode_attention(
                q[sel], cached, wkv_b, jnp.asarray(lengths[sel]), dn=dn,
                r=r, scale_dim=scale_dim)
            err = float(jnp.max(
                jnp.linalg.norm((got[sel] - want).reshape(len(sel), -1),
                                axis=-1)
                / jnp.linalg.norm(want.reshape(len(sel), -1), axis=-1)))
            if scale_dim is None:
                worst = float(np.maximum(worst, err))
            else:
                control = float(np.minimum(control, err))
    return {"latent_kernel_rel_err": worst,
            "latent_kernel_control": control,
            "latent_kernel_dead_rows_zero": int(zero),
            "latent_pool_layers": int(pool.shape[0])}


def engine_path(eng, prompt) -> dict:
    """``prompt`` through the engine's OWN compiled programs, on an idle
    engine, the way its loop calls them (``lib/replica_hybrid.py
    engine_handover``'s first two thirds): ``_get_prefill_paged(bucket,
    wave)`` at a wave the window warmed, the prompt in the wave's LAST
    row, its state entry in the packed column, an entry a request of the
    window left dirty; then ``_block_jit`` with the install arrays of
    ``_dispatch_block``: one block of greedy decode steps.  ``tokens``:
    the prompt, its first token and the block's tokens but the last (what
    the entry has absorbed); ``answered``: first token and block;
    ``left``: the entry's ``(state [K, dk, H*dv], tail)`` afterwards."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n, block = len(prompt), eng.block_size
    bucket = eng._bucket(n)
    wave = min((w for b, w in eng._prefill_jit if b == bucket and w > 1),
               default=1)
    dirty = np.asarray(jnp.any(eng._cache["gdn_state"][0] != 0, axis=(1, 2)))
    entry = next((e for e in range(2, eng.state_entries) if dirty[e]), 2)
    row = eng.num_slots - 1
    used = -(-(n + block) // eng.page_size)
    pages = eng.kv_pool_pages - 1 - np.arange(used)   # from the top, down

    packed = np.zeros((wave, eng.packed_width(bucket)), np.int32)
    packed[:, bucket] = 1
    packed[-1, :n], packed[-1, bucket] = prompt, n
    packed[-1, bucket + 2] = entry
    tables = np.zeros((wave, eng.max_pages), np.int32)
    tables[-1, :used] = pages
    firsts, eng._cache = eng._get_prefill_paged(bucket, wave)(
        eng.params, eng._cache, jnp.asarray(packed), jnp.asarray(tables),
        jax.random.PRNGKey(0))
    first = int(firsts[-1])

    slots = eng.num_slots
    meta = np.zeros((eng._meta_rows, slots), np.int32)
    meta[0] = np.arange(slots)        # all zeros but ``row``: redirects
    meta[1, row], meta[3, row] = n, entry
    lasts = np.zeros((slots,), np.int32)
    lasts[row] = first
    installs = np.zeros((slots, eng.max_pages), np.int32)
    installs[row, :used] = pages
    combined, eng._state, eng._cache = eng._block_jit(
        eng.params, eng._cache, eng._state, jnp.asarray(meta),
        jnp.asarray(lasts), jnp.asarray(installs))
    answered = [first] + [int(t) for t in np.asarray(combined)[
        :eng._rows * block].reshape(eng._rows, block)[row]]
    return {"tokens": list(prompt) + answered[:-1], "answered": answered,
            "left": (jnp.array(eng._cache["gdn_state"][:, entry]),
                     jnp.array(eng._cache["gdn_conv"][:, entry])),
            "entry": entry, "entry_was_dirty": int(dirty[entry]),
            "bucket": bucket, "wave": wave}


class KdaBenchLLMServer(HybridBenchLLMServer):

    def bench_reset_peaks(self) -> bool:
        """Start the engine's two high-water marks again (before the
        window); False where the program has none."""
        reset = getattr(self.engine.stats, "reset_peaks", None)
        if reset is not None:
            reset()
        return reset is not None

    def bench_reference(self, samples, config: dict) -> list:
        """A sample with ``faults``: its tokens (prompt, then what the
        engine streamed) through the engine's own model on the chip,
        against the module the configuration names, with the engine's
        own (served) weights: hidden states, logits, the routers, the
        state and tail entry 1 was left with after the prompt and at
        the end, the absorbed kernel on its pages; with the first such
        sample go the decode kernel on the entries as the window left
        them and the chunked prompt form.  A sample without: its PROMPT
        through the engine's own compiled prefill and decode block
        (``engine_path``), and what they left in its entry against the
        reference, the block's tokens against what the window streamed
        (greedy both times).  The engine is idle: every request of the
        window has finished."""
        import jax.numpy as jnp
        import numpy as np
        reference = importlib.import_module(config["program"]["reference"])
        eng = self.engine
        cfg = eng.cfg
        took, t0 = {}, time.perf_counter()

        def lap(name):
            nonlocal t0
            now = time.perf_counter()
            took[name], t0 = round(now - t0, 2), now

        def unpacked(state, tail):
            """A state entry's leaves, layer by layer, as the reference
            holds them."""
            return tuple(jnp.stack(parts) for parts in zip(*(
                reference.from_program_state(
                    state[k], tail[k], cfg.linear_value_heads,
                    cfg.linear_conv_kernel)
                for k in range(eng._state_layers))))
        weights = reference.from_program_params(eng.params)
        places = weights["layers"]
        # the kernels first: program_hidden overwrites entry 1
        kernels = kda_kernel_check(eng, reference)
        kernels.update(kda_chunk_check(eng, reference))
        lap("kernels")
        out = []
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            if not s.get("faults", True):
                own = engine_path(eng, list(s["prompt"]))
                lap(f"{i}.engine")
                m = {"context": len(own["tokens"]) + 1,
                     "bucket": own["bucket"], "handover_wave": own["wave"],
                     "handover_entry": own["entry"],
                     "handover_entry_was_dirty": own["entry_was_dirty"],
                     "handover_token_agree_share": float(np.mean([
                         a == b for a, b in zip(own["answered"],
                                                s["tokens"])])),
                     **reference.handover_check(
                         weights, jnp.asarray(own["tokens"]),
                         unpacked(*own["left"]), config)}
                lap(f"{i}.reference")
                out.append(m)
                continue
            seq = (list(s["prompt"]) + list(s["tokens"]))[:-1]
            got = program_hidden(eng, seq, n, places)
            rows, got_logits = program_logits(eng, got["hidden"], n)
            m = {"context": n + len(s["tokens"]), "bucket": got["bucket"],
                 "kda_layers": eng._state_layers,
                 "served_token_agree_share": served_token_agreement(
                     eng, got["hidden"][n - 1:], s["tokens"])}
            lap(f"{i}.program")
            m.update(reference.router_check(weights, got["router_in"],
                                            got["router_out"]))
            m.update(reference.hidden_check(
                weights, jnp.asarray(seq), got["hidden"], config,
                n_prompt=n, got_logits=got_logits,
                logit_rows=jnp.asarray(rows),
                left={when: unpacked(*held)
                      for when, held in got["left"].items()}))
            lap(f"{i}.reference")
            m.update(latent_kernel_check(eng, reference, weights,
                                         got["pages"], len(seq)))
            lap(f"{i}.latent")
            if kernels:
                m.update(kernels)
                kernels = None
            out.append(m)
        if out:
            out[-1]["took_s"] = took
        return out

    def device_info(self) -> dict:
        """``LLMServer.device_info`` and which implementations the
        recurrent decode step and the expert layer's resolve to here."""
        from ray_tpu.ops.gated_delta import resolve_gdn_impl
        from ray_tpu.ops.moe import expert_kernel_applies
        from ray_tpu.serve.llm import LLMServer
        eng = self.engine
        cfg = eng.cfg
        return {**LLMServer.device_info(self),
                "kda_impl": resolve_gdn_impl(cfg.linear_value_heads,
                                             cfg.linear_value_head_dim),
                "moe_impl": "tpu" if expert_kernel_applies(
                    eng._rows * cfg.moe_top_k, cfg.d_model, cfg.moe_d_ff)
                else "xla"}
