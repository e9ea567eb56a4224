"""``BenchLLMServer`` for a configuration that names its own reference
(``program.reference`` in the configuration's file, see
``chipbench/README-arch.md``): the serving path, the warm-up, the trace
and the facts are inherited untouched; only the reference check differs,
because only it knows the block.

The check compares NUMBERS, on the chip, at the sizes the cell times:
``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, pool and page tables (the prompt by the paged prefill
path at the engine's bucket, every later position as one decode step in
the engine's decode shape, Pallas kernel and all) and returns the hidden
states, the router's inputs and outputs in every layer, and how many of
the served tokens are that program's largest logit; the reference module
holds them to float32.  ``window_kernel_check`` then runs the paged
decode kernel, with the window, on the pages that request left in the
pool, against plain attention over the same keys.
"""

import functools
import importlib
import time

from chipbench.lib.replica import BenchLLMServer

HEAD_ROWS = 512


def _captured(mdl, method: str) -> bool:
    """The router's input (the block's normalised attention input) and
    its logits, in every layer, where the block has a router."""
    return method == "router_logits" or (
        method == "__call__" and mdl.name == "attn_norm")


def _router_io(intermediates):
    """``(h [L, B, T, d], r [L, B, T, E])`` from a captured tree, or
    None where the model has no router of its own."""
    import jax
    import jax.numpy as jnp
    found = {"attn_norm": [], "router_logits": []}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        key = jax.tree_util.keystr(path)
        for name, into in found.items():
            if name in key:
                into.append(leaf.reshape((-1,) + leaf.shape[-3:]))
    if not found["router_logits"]:
        return None
    return tuple(jnp.concatenate(found[n]) for n in
                 ("attn_norm", "router_logits"))


def program_hidden(eng, tokens, n_prompt: int, model=None) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool
    (pages 1.. of an idle engine): positions ``< n_prompt`` in one paged
    prefill at the engine's bucket, the others one decode step each with
    the request in row 0 of the engine's rows.  ``hidden [S, d]`` is
    post-final-norm, in the model's dtype; ``router_in [L, S, d]`` and
    ``router_out [L, S, E]`` where the block routes.  ``model`` stands
    in for the engine's (a probe's wrong program)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = model or eng.model
    bucket = eng._bucket(n_prompt)
    table = np.zeros((1, eng.max_pages), np.int32)
    used = -(-len(tokens) // eng.page_size)
    table[0, :used] = 1 + np.arange(used)
    tables = np.zeros((eng._rows, eng.max_pages), np.int32)
    tables[0] = table[0]

    def apply(params, cache, toks, poss, tabs):
        out, mut = model.apply(
            {"params": params, "cache": cache}, toks, poss,
            block_tables=tabs, return_hidden=True,
            mutable=["cache", "intermediates"],
            capture_intermediates=_captured)
        return out, _router_io(mut["intermediates"]), mut["cache"]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, toks):
        out, io, cache = apply(params, cache, toks,
                               jnp.arange(bucket)[None], jnp.asarray(table))
        return cache, (out[0], None if io is None
                       else tuple(a[:, 0] for a in io))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, cache, toks, poss):
        rows = jnp.zeros((eng._rows, 1), jnp.int32)

        def one(cache, xs):
            out, io, cache = apply(params, cache, rows.at[0, 0].set(xs[0]),
                                   rows.at[0, 0].set(xs[1]),
                                   jnp.asarray(tables))
            return cache, (out[0, 0], None if io is None
                           else tuple(a[:, 0, 0] for a in io))
        return jax.lax.scan(one, cache, (toks, poss))

    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    eng._cache, (hid, io) = prefill(eng.params, eng._cache,
                                    jnp.asarray(padded))
    hid = hid[:n_prompt]
    io = io and tuple(a[:, :n_prompt] for a in io)
    if len(tokens) > n_prompt:
        eng._cache, (more, io2) = decode(
            eng.params, eng._cache, jnp.asarray(tokens[n_prompt:], jnp.int32),
            jnp.arange(n_prompt, len(tokens), dtype=jnp.int32))
        hid = jnp.concatenate([hid, more])
        io = io and tuple(jnp.concatenate([a, jnp.moveaxis(b, 0, 1)], 1)
                          for a, b in zip(io, io2))
    out = {"hidden": hid, "pages": table[0, :used]}
    if io:
        out["router_in"], out["router_out"] = io
    return out


def served_token_agreement(eng, hidden, tokens) -> float:
    """Share of the served ``tokens [N]`` that are the largest logit of
    the program's own head on ``hidden [N, d]``: what ties the tokens
    the engine streamed to the numbers the reference is held against."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt import output_logits
    head = jax.jit(lambda p, x: jnp.argmax(
        output_logits(eng.cfg, p, x), -1))
    got = jnp.concatenate([head(eng.params, hidden[lo:lo + HEAD_ROWS])
                           for lo in range(0, len(tokens), HEAD_ROWS)])
    return float(jnp.mean(got == jnp.asarray(tokens)))


def window_kernel_check(eng, reference, pages, context: int) -> dict:
    """The paged decode kernel under the model's window, on the pages a
    scored request of ``context`` positions left in the pool, in the
    engine's decode shape: every row reads the same pages, at lengths
    from one position to the whole context, in each layer that has the
    window.  ``window_kernel_rel_err`` is the largest row's
    |kernel - plain| / |plain| against ``reference.decode_attention``
    over the same keys; ``window_kernel_control`` is the same against
    plain attention WITHOUT the window (what a kernel that ignores it
    would read).  ``window_kernel_time_ratio`` is the kernel's time with
    the window over its time without, every row at ``max_seq_len``:
    about ``window_kernel_pages_ratio`` where its page loop starts at
    the window, 1 where it only masks."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import paged_attention
    cfg = eng.cfg
    pool = next(a for a in jax.tree.leaves(eng._cache)
                if eng._is_pool_leaf(a))
    rows, hd = eng._rows, cfg.head_dim
    window = int(cfg.sliding_window)
    # half the rows anywhere in the context, half around and past the
    # window's edge
    lengths = np.concatenate([
        np.linspace(1, context, rows // 2),
        np.linspace(max(1, window - eng.page_size), context,
                    rows - rows // 2)]).astype(np.int32)
    tables = np.zeros((rows, eng.max_pages), np.int32)
    tables[:, :len(pages)] = pages
    q = jax.random.normal(jax.random.PRNGKey(0),
                          (rows, cfg.n_heads, hd), jnp.float32
                          ).astype(cfg.dtype)
    layout = cfg.window_layout or (1,) * cfg.n_layers
    kernel = jax.jit(lambda pool, layer: paged_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(lengths), layer=layer,
        window=jnp.int32(window)))
    worst = control = 0.0
    for layer in [i for i in range(cfg.n_layers) if layout[i]]:
        got = kernel(pool, jnp.int32(layer)).astype(jnp.float32)
        kv = pool[layer, jnp.asarray(pages)]        # [n, KV, page, 2 hd]
        kv = jnp.moveaxis(kv, 1, 2).reshape(-1, kv.shape[1], 2 * hd)
        for win in (window, None):
            want = reference.decode_attention(
                q, kv[..., :hd], kv[..., hd:], jnp.asarray(lengths), win)
            err = float(jnp.max(
                jnp.linalg.norm((got - want).reshape(rows, -1), axis=-1)
                / jnp.linalg.norm(want.reshape(rows, -1), axis=-1)))
            if win is None:
                control = max(control, err)
            else:
                worst = max(worst, err)
    # the device's own word on where the page loop starts: every row at
    # max_seq_len (the request's pages over and over: time does not care
    # what they hold), with the window and with none
    full = np.full((rows,), cfg.max_seq_len, np.int32)
    timed = jax.jit(lambda pool, win: paged_attention(
        q, pool, jnp.asarray(np.resize(pages, tables.shape)),
        jnp.asarray(full), layer=0, window=win))

    def clock(win, n=10, best_of=3):
        timed(pool, win).block_until_ready()
        took = []
        for _ in range(best_of):      # the least: a pause hits one at most
            t0 = time.perf_counter()
            for _ in range(n):
                out = timed(pool, win)
            out.block_until_ready()
            took.append((time.perf_counter() - t0) / n)
        return min(took)
    ps = eng.page_size
    return {"window_kernel_rel_err": worst,
            "window_kernel_control": control,
            "window_kernel_rows_past": int((lengths > window).sum()),
            "window_kernel_time_ratio":
                clock(jnp.int32(window)) / clock(jnp.int32(1 << 30)),
            "window_kernel_pages_ratio":
                1 - ((cfg.max_seq_len - window) // ps)
                / -(-cfg.max_seq_len // ps)}


class ArchBenchLLMServer(BenchLLMServer):

    def bench_reference(self, samples, config: dict) -> list:
        """Each sample's tokens (prompt, then what the engine streamed)
        through the engine's own model on the chip, against the module
        the configuration names, with the engine's own (served) weights.
        ``config`` is the configuration's file as loaded.  The engine is
        idle: every request of the window has finished."""
        import jax.numpy as jnp
        reference = importlib.import_module(config["program"]["reference"])
        eng = self.engine
        weights = reference.from_program_params(eng.params)
        out = []
        for s in samples:
            n = len(s["prompt"])
            seq = (list(s["prompt"]) + list(s["tokens"]))[:-1]
            got = program_hidden(eng, seq, n)
            m = {"context": n + len(s["tokens"]),
                 "served_token_agree_share": served_token_agreement(
                     eng, got["hidden"][n - 1:], s["tokens"])}
            m.update(reference.hidden_check(weights, jnp.asarray(seq),
                                            got["hidden"], config))
            if "router_out" in got:
                m.update(reference.router_check(
                    weights, got["router_in"], got["router_out"]))
            if eng.cfg.sliding_window and len(seq) > eng.cfg.sliding_window:
                m.update(window_kernel_check(eng, reference, got["pages"],
                                             len(seq)))
            out.append(m)
        return out
