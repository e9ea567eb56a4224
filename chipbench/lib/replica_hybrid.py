"""``BenchLLMServer`` for a configuration with layers whose state is not
pages (``chipbench/README-hybrid.md``): the serving path, the trace and
the facts are inherited untouched; the warm-up and the reference check
differ, because both have to know that a prefill program of such a model
is told each prompt's REAL length and where its state goes.

The check compares NUMBERS, on the chip, at the sizes the cell times.
``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, pool, state entries and page tables: the prompt by
the paged prefill path at the engine's bucket with its real length,
padded as the engine pads it; every later position as one decode step in
the engine's decode shape (``gdn_decode`` and ``paged_attention_decode``
and all), the request in row 0 on state entry 1.  The reference module
holds the hidden states to float32 and to references made wrong on
purpose.  Those programs are the check's own (the engine's return
tokens, not hidden states), so ``engine_handover`` runs a prompt through
the ENGINE'S compiled programs, the ones the window timed
(``engine_prefill`` with the packed column of entries, then
``engine_decode_block`` with install rows), and reads hidden states from
the pages and the entry they left.  ``gdn_kernel_check`` runs the decode
kernel for 256 steps on the states the window's requests left in the
entries, against the float32 recurrence, and looks at the entries of the
rows it was told are dead.
"""

import functools
import importlib
import time

from chipbench.lib.replica import BenchLLMServer
from chipbench.lib.replica_arch import served_token_agreement

KERNEL_STEPS = 256
HANDOVER_STEPS = 32
DECODE_CHUNK = 128
LOADERS = 4


def _apply(model, params, cache, toks, poss, tabs, **recurrent):
    """``(hidden, cache)`` of one call of the engine's model on its
    cache: post-final-norm hidden states, the cache updated."""
    out, mut = model.apply(
        {"params": params, "cache": cache}, toks, poss,
        block_tables=tabs, return_hidden=True, mutable=["cache"],
        **recurrent)
    return out, mut["cache"]


def program_hidden(eng, tokens, n_prompt: int) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool and
    state entries (pages 1.., entry 1 of an idle engine): positions ``<
    n_prompt`` in one paged prefill at the engine's bucket, told the
    real length; the others one decode step each, the request in row 0
    of the engine's rows.  ``hidden [S, d]`` is post-final-norm, in the
    model's dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = eng.model
    bucket = eng._bucket(n_prompt)
    table = np.zeros((1, eng.max_pages), np.int32)
    used = -(-len(tokens) // eng.page_size)
    table[0, :used] = 1 + np.arange(used)
    tables = np.zeros((eng._rows, eng.max_pages), np.int32)
    tables[0] = table[0]
    entries = np.zeros((eng._rows,), np.int32)
    entries[0] = 1

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, toks):
        out, cache = _apply(model, params, cache, toks,
                            jnp.arange(bucket)[None], jnp.asarray(table),
                            lengths=jnp.asarray([n_prompt], jnp.int32),
                            state_rows=jnp.asarray([1], jnp.int32))
        return cache, out[0]

    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    eng._cache, hid = prefill(eng.params, eng._cache, jnp.asarray(padded))
    hid = [hid[:n_prompt]]
    # DECODE_CHUNK steps a call, so that requests of any length share
    # one compiled program; the last call runs on past the request's
    # end (token 0, the positions after it, whose pages are scratch)
    # and those rows are dropped
    rest = np.zeros((-(-(len(tokens) - n_prompt) // DECODE_CHUNK)
                     * DECODE_CHUNK,), np.int32)
    rest[:len(tokens) - n_prompt] = tokens[n_prompt:]
    poss = np.minimum(n_prompt + np.arange(len(rest)),
                      eng.cfg.max_seq_len - 1).astype(np.int32)
    for lo in range(0, len(rest), DECODE_CHUNK):
        eng._cache, more = _decode_chunk_of(eng)(
            eng.params, eng._cache, jnp.asarray(rest[lo:lo + DECODE_CHUNK]),
            jnp.asarray(poss[lo:lo + DECODE_CHUNK]), jnp.asarray(tables),
            jnp.asarray(entries))
        hid.append(more)
    return {"hidden": jnp.concatenate(hid)[:len(tokens)], "bucket": bucket}


def _decode_chunk_of(eng):
    """``fn(params, cache, toks [K], poss [K], tables, entries) ->
    (cache, hidden [K, d])``: one decode step a token in the engine's
    decode shape, the request in row 0.  One jitted function an engine,
    so that a second request finds the first one's program."""
    import jax
    import jax.numpy as jnp
    fn = getattr(eng, "_bench_decode_chunk", None)
    if fn is None:
        model, n_rows = eng.model, eng._rows

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, poss, tables, entries):
            rows = jnp.zeros((n_rows, 1), jnp.int32)

            def one(cache, xs):
                out, cache = _apply(model, params, cache,
                                    rows.at[0, 0].set(xs[0]),
                                    rows.at[0, 0].set(xs[1]), tables,
                                    state_rows=entries)
                return cache, out[0, 0]
            return jax.lax.scan(one, cache, (toks, poss))
        eng._bench_decode_chunk = fn
    return fn


def engine_handover(eng, prompt, steps: int = HANDOVER_STEPS) -> dict:
    """``prompt`` through the engine's OWN compiled programs, on an idle
    engine, the way its loop calls them: ``_get_prefill_paged(bucket,
    wave)`` at a wave the window warmed, the prompt in the wave's LAST
    row (the others are the pad rows of ``_dispatch_prefill_waves``),
    its state entry in the packed column, an entry that a request of
    the window used and left dirty; then ``_block_jit`` with the install
    arrays of ``_dispatch_block`` (slot, position, entry, first token,
    table; every other slot redirected to scratch): one block of greedy
    decode steps.  What is held to the reference is what those two left
    behind: ``steps`` more positions decoded greedily by this check from
    THAT entry and THOSE pages, in the engine's decode shape.
    ``tokens``: the prompt, the engine's tokens and the check's;
    ``hidden [steps, d]``: the check's rows, the last of ``tokens``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.gpt import output_logits
    model, n, block = eng.model, len(prompt), eng.block_size
    bucket = eng._bucket(n)
    steps = min(steps, eng.cfg.max_seq_len - n - block)
    wave = min((w for b, w in eng._prefill_jit if b == bucket and w > 1),
               default=1)
    dirty = np.asarray(jnp.any(eng._cache["gdn_state"][0] != 0, axis=(1, 2)))
    entry = next((e for e in range(2, eng.state_entries) if dirty[e]), 2)
    row = eng.num_slots - 1
    used = -(-(n + block + steps) // eng.page_size)
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
    answered = np.asarray(combined)[:eng._rows * block].reshape(
        eng._rows, block)[row]

    tabs = np.zeros((eng._rows, eng.max_pages), np.int32)
    tabs[row] = installs[row]
    entries = np.zeros((eng._rows,), np.int32)
    entries[row] = entry

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, cache, token, poss):
        rows = jnp.zeros((eng._rows, 1), jnp.int32)

        def one(carry, pos):
            cache, token = carry
            out, cache = _apply(model, params, cache,
                                rows.at[row, 0].set(token),
                                rows.at[row, 0].set(pos), jnp.asarray(tabs),
                                state_rows=jnp.asarray(entries))
            nxt = jnp.argmax(output_logits(
                eng.cfg, params, out[row]), -1)[0].astype(jnp.int32)
            return (cache, nxt), (out[row, 0], nxt)
        (cache, _), (hid, toks) = jax.lax.scan(one, (cache, token), poss)
        return cache, hid, toks
    eng._cache, hid, toks = decode(
        eng.params, eng._cache, jnp.int32(answered[-1]),
        jnp.arange(n + block, n + block + steps, dtype=jnp.int32))
    return {"tokens": list(prompt) + [first] + [int(t) for t in answered]
            + [int(t) for t in toks[:-1]],
            "answered": [first] + [int(t) for t in answered],
            "hidden": hid, "entry": entry, "entry_was_dirty": int(dirty[entry]),
            "row": row, "bucket": bucket, "wave": wave}


def gdn_kernel_check(eng, reference, steps: int = KERNEL_STEPS) -> dict:
    """``ops/gated_delta.py gdn_decode`` as the engine's decode block
    calls it (its rows, its stacked state leaf, in place), ``steps``
    steps in every linear layer, on the states the requests of the
    window left in the entries (row r on entry r + 1, the scratch row
    on scratch), two rows in three live.  ``gdn_kernel_rel_err``: the
    live rows' outputs against ``reference.recurrence_check``'s float32
    recurrence from the same states, worst layer; its control: a
    recurrence that keeps its state in bfloat16.  Two more witnesses,
    on the last layer: ``gdn_kernel_f64_rel_err``, the first live row
    against the recurrence in numpy float64 (other arithmetic than the
    float32 reference's, which the kernel equals bit for bit: this one
    it cannot), and ``gdn_kernel_bf16_held_control``, the KERNEL run
    again with its state leaf rounded to bfloat16 after every step,
    against the float32 recurrence: what the reading does when the
    kernel, not the reference, is the one made wrong.
    ``dead_rows_untouched``: 1 where the entries of the rows that were
    not live are bit for bit what they were.  ``gdn_step_us``: the
    kernel's wall time a layer step here (the traced run's device time
    is the metric; this is for the log)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.gated_delta import gdn_decode, unpack_state
    cfg = eng.cfg
    rows, h = eng._rows, cfg.linear_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    n_entries = eng.state_entries
    entries = np.where(np.arange(rows) + 1 < n_entries,
                       np.arange(rows) + 1, 0).astype(np.int32)
    live = (np.arange(rows) % 3 != 2) & (entries > 0)
    dead_entries = entries[~live & (entries > 0)]

    @jax.jit
    def inputs(key):
        """Unit keys, scaled unit queries, decays and write strengths
        over the model's own ranges; made on the device (as arguments
        of ``run``, not constants of it: a gigabyte of captured
        constants holds the replica's interpreter for seconds, and the
        controller takes a replica that misses three health checks for
        dead)."""
        ks = jax.random.split(key, 5)
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
        return ((unit(jax.random.normal(ks[0], (steps, rows, h, dk)))
                 * dk ** -0.5).astype(cfg.dtype),
                unit(jax.random.normal(ks[1], (steps, rows, h, dk))
                     ).astype(cfg.dtype),
                jax.random.normal(ks[2], (steps, rows, h, dv)
                                  ).astype(cfg.dtype),
                -jnp.exp(jax.random.uniform(ks[3], (steps, rows, h),
                                            minval=-7.0, maxval=0.5)),
                2.0 * jax.nn.sigmoid(jax.random.normal(
                    ks[4], (steps, rows, h))))
    q, k, v, g, beta = inputs(jax.random.PRNGKey(0))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(state, layer, *xs):
        def one(state, x):
            o, state = gdn_decode(*x, state, jnp.asarray(entries),
                                  jnp.asarray(live), layer=layer)
            return state, o
        return jax.lax.scan(one, state, xs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_held(state, layer, *xs):
        """``run`` by a kernel that keeps its state in bfloat16."""
        def one(state, x):
            o, state = gdn_decode(*x, state, jnp.asarray(entries),
                                  jnp.asarray(live), layer=layer)
            # reduce_precision, not astype there and back: the TPU
            # compiler drops a pair of converts as excess precision it
            # may keep, and the control then read 0.0 (PERF.md, PR 33);
            # one dynamic_update_slice, not a scatter of the layer
            held = jax.lax.reduce_precision(jax.lax.dynamic_index_in_dim(
                state, layer, 0, keepdims=True), exponent_bits=8,
                mantissa_bits=7)
            return jax.lax.dynamic_update_index_in_dim(
                state, held, layer, 0), o
        return jax.lax.scan(one, state, xs)

    sel = jnp.asarray(np.flatnonzero(live))
    against = lambda o, state0: reference.recurrence_check(  # noqa: E731
        o[:, sel], q[:, sel], k[:, sel], v[:, sel], g[:, sel], beta[:, sel],
        state0)
    worst = control = 0.0
    untouched, took = True, []
    for layer in range(eng._state_layers):
        state = eng._cache["gdn_state"]
        before = state[layer]
        state0 = unpack_state(before[jnp.asarray(entries[live])], h)
        t0 = time.perf_counter()
        state, o = run(state, jnp.int32(layer), q, k, v, g, beta)
        o.block_until_ready()
        took.append(time.perf_counter() - t0)
        eng._cache["gdn_state"] = state
        untouched &= bool(jnp.array_equal(
            state[layer][jnp.asarray(dead_entries)],
            before[jnp.asarray(dead_entries)]))
        m = against(o, state0)
        # np.maximum, not max(): a NaN reading has to come out as NaN
        # (and so outside every limit), where max(0.0, nan) is 0.0
        worst = float(np.maximum(worst, m["gdn_kernel_rel_err"]))
        control = float(np.maximum(
            control, m["gdn_kernel_bf16_state_control"]))
    # the last layer once more: one row in float64, then the kernel
    # with its state held in bfloat16 from where the run above left it
    r = int(sel[0])
    want = reference.recurrence_f64(q[:, r], k[:, r], v[:, r], g[:, r],
                                    beta[:, r], state0[0])
    f64_err = float(np.linalg.norm(np.asarray(o[:, r], np.float64) - want)
                    / np.linalg.norm(want))
    state = eng._cache["gdn_state"]
    state0 = unpack_state(state[layer][jnp.asarray(entries[live])], h)
    eng._cache["gdn_state"], o = run_held(
        state, jnp.int32(layer), q, k, v, g, beta)
    held = against(o, state0)["gdn_kernel_rel_err"]
    return {"gdn_kernel_rel_err": worst,
            "gdn_kernel_bf16_state_control": control,
            "gdn_kernel_f64_rel_err": f64_err,
            "gdn_kernel_bf16_held_control": held,
            "gdn_kernel_steps": steps, "gdn_kernel_live_rows": int(live.sum()),
            "dead_rows_untouched": int(untouched),
            # the first layer's call compiled
            "gdn_step_us": 1e6 * min(took[1:] or took) / steps}


class HybridBenchLLMServer(BenchLLMServer):

    def __init__(self, *args, **kwargs):
        t0 = time.perf_counter()
        super().__init__(*args, **kwargs)
        self._init_s = time.perf_counter() - t0

    @staticmethod
    def _load_params(cfg, checkpoint, seed: int):
        """``LLMServer._load_params`` with the random weights made by
        ONE compiled program (the persistent cache keeps it), not
        initialiser by initialiser: op-by-op programs are recompiled in
        every process.  Which weights a seed gives is the benchmark's
        affair; the served path starts at the engine."""
        if checkpoint:
            return BenchLLMServer._load_params(cfg, checkpoint, seed)
        import jax
        import jax.numpy as jnp
        from ray_tpu.models.gpt import GPT
        t0 = time.perf_counter()
        init = jax.jit(lambda key: GPT(cfg, decode=True).init(
            key, jnp.zeros((1, 1), jnp.int32))["params"])
        params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
        HybridBenchLLMServer._params_s = time.perf_counter() - t0
        return params

    def bench_warm(self, pairs, concat_sizes) -> dict:
        """``BenchLLMServer.bench_warm`` with the prefill programs'
        operand as THIS engine packs it (``LLMEngine.packed_width``: a
        model with recurrent layers gets a column of state entries)."""
        import concurrent.futures
        import itertools

        import jax
        import jax.numpy as jnp
        import numpy as np
        eng = self.engine
        t0 = time.perf_counter()
        rng = jax.random.PRNGKey(0)

        def operands(bucket, wave):
            packed = np.zeros((wave, eng.packed_width(bucket)), np.int32)
            packed[:, bucket] = 1
            return (eng.params, eng._cache, jnp.asarray(packed),
                    jnp.zeros((wave, eng.max_pages), jnp.int32), rng)
        # each program is built ahead of its first call, LOADERS at a
        # time (the call then finds it: jit keeps what
        # ``lower().compile()`` made for the same operands).  Compiling
        # 25 took 254 s so, 343 s one after the other; read back from
        # the compile cache they take as long either way, which is
        # Python tracing them: 6.4 s a program on the chip's host, 9 s
        # the widest, 10 s on a slow host (PERF.md section 6, PRs 33
        # and 40), so the mix warms no program its windows cannot form
        fns = [eng._get_prefill_paged(b, w) for b, w in pairs]

        def build(fn, pair) -> float:
            """Seconds this program took to trace, lower and compile or
            load, LOADERS of them sharing one interpreter (for the log:
            which programs a shorter warm-up list saves most on)."""
            t = time.perf_counter()
            fn.lower(*operands(*pair)).compile()
            return round(time.perf_counter() - t, 2)

        with concurrent.futures.ThreadPoolExecutor(LOADERS) as pool:
            block = pool.submit(lambda: eng._block_jit.lower(
                eng.params, eng._cache, eng._state, *eng._no_admit
            ).compile())
            each = list(pool.map(build, fns, pairs))
            block.result()
        built = time.perf_counter() - t0
        for fn, pair in zip(fns, pairs):
            _, eng._cache = fn(*operands(*pair))
        eng.warmup(prompt_lens=())           # the block program alone
        combos = [tuple(c) for c in concat_sizes.get("exact", [])] + [
            c for k, sizes in concat_sizes.get("products", [])
            for c in itertools.product(sizes, repeat=k)]

        def join(combo):
            np.asarray(jnp.concatenate(
                [jnp.zeros((w,), jnp.int32) for w in combo]))

        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            list(pool.map(join, combos))
        return {"seconds": time.perf_counter() - t0, "programs": len(pairs),
                "joins": len(combos), "built_s": built, "built_each_s": each,
                "init_s": self._init_s,
                "params_s": getattr(self, "_params_s", None)}

    def bench_reference(self, samples, config: dict) -> list:
        """Each sample's tokens (prompt, then what the engine streamed)
        through the engine's own model on the chip, against the module
        the configuration names, with the engine's own (served) weights;
        then, once, the decode kernel on the entries as the window left
        them, and the shortest sample's prompt through the engine's own
        compiled programs (``engine_handover``); the readings of both
        go with the last sample.  The engine is idle: every request of
        the window has finished."""
        import jax.numpy as jnp
        import numpy as np
        reference = importlib.import_module(config["program"]["reference"])
        eng = self.engine
        took, t0 = {}, time.perf_counter()

        def lap(name):
            """Seconds since the last lap, under ``name`` (for the log:
            a run has to end well inside the driver's limit)."""
            nonlocal t0
            now = time.perf_counter()
            took[name], t0 = round(now - t0, 2), now
        weights = reference.from_program_params(eng.params)
        # the kernel first: program_hidden overwrites entry 1
        kernel = gdn_kernel_check(eng, reference)
        lap("kernel")
        out = []
        for s, which in zip(samples, ("first", "second")):
            n = len(s["prompt"])
            seq = (list(s["prompt"]) + list(s["tokens"]))[:-1]
            got = program_hidden(eng, seq, n)
            m = {"context": n + len(s["tokens"]), "bucket": got["bucket"],
                 "served_token_agree_share": served_token_agreement(
                     eng, got["hidden"][n - 1:], s["tokens"])}
            lap(which + ".program")
            m.update(reference.hidden_check(
                weights, jnp.asarray(seq), got["hidden"], config,
                n_prompt=n, bucket=got["bucket"]))
            lap(which + ".reference")
            out.append(m)
        if out:
            # the engine's own compiled programs, on the shortest prompt
            s = samples[0]
            own = engine_handover(eng, list(s["prompt"]))
            lap("handover.program")
            handover = reference.handover_check(
                weights, jnp.asarray(own["tokens"]), own["hidden"], config,
                n_prompt=len(s["prompt"]))
            lap("handover.reference")
            handover.update(
                handover_entry=own["entry"],
                handover_entry_was_dirty=own["entry_was_dirty"],
                handover_wave=own["wave"], handover_row=own["row"],
                # for the log: the window served this prompt in another
                # wave and row, and one flipped token flips the rest
                handover_token_agree_share=float(np.mean([
                    a == b for a, b in zip(own["answered"], s["tokens"])])))
            out[-1].update(kernel, **handover, took_s=took)
        return out

    def device_info(self) -> dict:
        """``LLMServer.device_info`` and which implementation the
        recurrent decode step resolves to here."""
        from ray_tpu.ops.gated_delta import resolve_gdn_impl
        cfg = self.engine.cfg
        return {**super().device_info(),
                "gdn_impl": resolve_gdn_impl(cfg.linear_value_heads,
                                             cfg.linear_value_head_dim)}
