"""``BenchLLMServer`` for a configuration whose cache row is LATENT (one
vector a token for all heads, ``chipbench/README-latent.md``) and whose
router reads the feed-forward's own input.  The serving path, the trace
and the facts are inherited untouched.  The warm-up, the jitted
parameter initialisation and the trace without the Python call tracer
are ``lib/replica_hybrid.py HybridBenchLLMServer``'s (inherited: they
ask the engine for its operand's width and know nothing of recurrent
layers); the reference check is this file's, because

- ``lib/replica_arch.py _captured`` takes the router's input at
  ``attn_norm`` (SmallThinker routes from the attention's input); here
  it is the feed-forward's normalised input, ``mlp_norm`` of the expert
  stack;
- its kernel check reads a K|V pool under a window; here a page holds
  latent rows, keys and values the same bytes.

The check compares NUMBERS, on the chip, at the sizes the cell times:
``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, pool and page tables (the prompt by the paged
prefill path at the engine's bucket: EXPANDED attention; every later
position as one decode step in the engine's decode shape: ABSORBED, the
Pallas kernel and ``moe_experts_decode`` and all) and returns the hidden
states and the routers' inputs and outputs; the reference module holds
them to float32 and to references made wrong on purpose.
``latent_kernel_check`` then runs the absorbed decode
(``models/gpt.py absorbed_attention``: the kernel between its two
projections) on the pages that request left in the pool, against plain
EXPANDED attention over the same rows in float32.
"""

import functools
import importlib
import time

from chipbench.lib.replica_arch import served_token_agreement
from chipbench.lib.replica_hybrid import HybridBenchLLMServer

DECODE_CHUNK = 128


def _captured(mdl, method: str) -> bool:
    """The routers' inputs (every block's ``mlp_norm``; ``_router_io``
    keeps the expert stack's) and logits."""
    return method == "router_logits" or (
        method == "__call__" and mdl.name == "mlp_norm")


def _router_io(intermediates):
    """``(z [L, B, T, d], r [L, B, T, E])`` of the expert stack from a
    captured tree (the dense prefix has an ``mlp_norm`` and no router:
    left out), or None where nothing routes."""
    import jax
    import jax.numpy as jnp
    found = {"mlp_norm": [], "router_logits": []}
    for path, leaf in jax.tree_util.tree_leaves_with_path(intermediates):
        key = jax.tree_util.keystr(path)
        if "dense_block" in key:
            continue
        for name, into in found.items():
            if name in key:
                into.append(leaf.reshape((-1,) + leaf.shape[-3:]))
    if not found["router_logits"]:
        return None
    return tuple(jnp.concatenate(found[n]) for n in
                 ("mlp_norm", "router_logits"))


def _apply(model, params, cache, toks, poss, tabs):
    out, mut = model.apply(
        {"params": params, "cache": cache}, toks, poss, block_tables=tabs,
        return_hidden=True, mutable=["cache", "intermediates"],
        capture_intermediates=_captured)
    return out, _router_io(mut["intermediates"]), mut["cache"]


def _decode_chunk_of(eng, model):
    """``fn(params, cache, toks [K], poss [K], tables) -> (cache, (hidden
    [K, d], (z [K, L, d], r [K, L, E])))``: one decode step a token in
    the engine's decode shape, the request in row 0.  One jitted
    function an engine and model, so that a second request finds the
    first one's program."""
    import jax
    import jax.numpy as jnp
    key = "_bench_decode_chunk_" + str(id(model))
    fn = getattr(eng, key, None)
    if fn is None:
        n_rows = eng._rows

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, poss, tables):
            rows = jnp.zeros((n_rows, 1), jnp.int32)

            def one(cache, xs):
                out, io, cache = _apply(
                    model, params, cache, rows.at[0, 0].set(xs[0]),
                    rows.at[0, 0].set(xs[1]), tables)
                return cache, (out[0, 0], None if io is None
                               else tuple(a[:, 0, 0] for a in io))
            return jax.lax.scan(one, cache, (toks, poss))
        setattr(eng, key, fn)
    return fn


def program_hidden(eng, tokens, n_prompt: int, model=None) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool
    (pages 1.. of an idle engine): positions ``< n_prompt`` in one paged
    prefill at the engine's bucket, the others one decode step each with
    the request in row 0 of the engine's rows, ``DECODE_CHUNK`` steps a
    call (the last call runs on past the end at token 0, whose rows are
    dropped).  ``hidden [S, d]`` is post-final-norm, in the model's
    dtype; ``router_in [L, S, d]`` and ``router_out [L, S, E]`` of the
    expert layers.  ``model`` stands in for the engine's (a probe's
    wrong program)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    model = model or eng.model
    bucket = eng._bucket(n_prompt)
    n_rest = len(tokens) - n_prompt
    padded_rest = -(-n_rest // DECODE_CHUNK) * DECODE_CHUNK
    # steps past the end write at positions clipped to the last one
    used = min(-(-(n_prompt + padded_rest) // eng.page_size), eng.max_pages)
    table = np.zeros((1, eng.max_pages), np.int32)
    table[0, :used] = 1 + np.arange(used)
    tables = np.zeros((eng._rows, eng.max_pages), np.int32)
    tables[0] = table[0]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, toks):
        out, io, cache = _apply(model, params, cache, toks,
                                jnp.arange(bucket)[None], jnp.asarray(table))
        return cache, (out[0], None if io is None
                       else tuple(a[:, 0] for a in io))

    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = tokens[:n_prompt]
    eng._cache, (hid, io) = prefill(eng.params, eng._cache,
                                    jnp.asarray(padded))
    hid = [hid[:n_prompt]]
    io = [io and tuple(a[:, :n_prompt] for a in io)]
    rest = np.zeros((padded_rest,), np.int32)
    rest[:n_rest] = tokens[n_prompt:]
    poss = np.minimum(n_prompt + np.arange(padded_rest),
                      eng.cfg.max_seq_len - 1).astype(np.int32)
    step = _decode_chunk_of(eng, model)
    for lo in range(0, padded_rest, DECODE_CHUNK):
        eng._cache, (more, io2) = step(
            eng.params, eng._cache, jnp.asarray(rest[lo:lo + DECODE_CHUNK]),
            jnp.asarray(poss[lo:lo + DECODE_CHUNK]), jnp.asarray(tables))
        hid.append(more)
        io.append(io2 and tuple(jnp.moveaxis(a, 0, 1) for a in io2))
    out = {"hidden": jnp.concatenate(hid)[:len(tokens)], "bucket": bucket,
           "pages": table[0, :-(-len(tokens) // eng.page_size)]}
    if io[0]:
        out["router_in"], out["router_out"] = (
            jnp.concatenate([part[i] for part in io], 1)[:, :len(tokens)]
            for i in (0, 1))
    return out


def latent_kernel_check(eng, reference, pages, context: int) -> dict:
    """The absorbed decode on the pages a scored request of ``context``
    positions left in the pool, in the engine's decode shape: every row
    reads the same pages, at lengths from one position to the whole
    context, one row in four dead, in every layer.
    ``latent_kernel_rel_err`` is the largest live row's |program - plain|
    / |plain| against ``reference.decode_attention``: EXPANDED attention
    over the same rows in float32 with the layer's own ``wkv_b``;
    ``latent_kernel_control`` the same against that attention with the
    softmax scale of the un-rotated part alone (a kernel given the wrong
    scale).  ``latent_kernel_dead_rows_zero``: 1 where every dead row
    came back zero.  ``latent_kernel_us``: the call's wall time a layer
    (the traced run's device time is the metric; this is for the log)."""
    import flax.linen as nn
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
    p = nn.unbox(eng.params)
    n_dense = cfg.first_dense_layers
    kernel = jax.jit(lambda pool, wkv_b, layer: absorbed_attention(
        cfg, q, wkv_b, pool, jnp.asarray(tables), jnp.asarray(lengths),
        layer=layer, live=jnp.asarray(live)))
    worst = control = 0.0
    zero, took = True, []
    sel = np.flatnonzero(live)
    for layer in range(cfg.n_layers):
        stack, at = (("dense_blocks", layer) if layer < n_dense
                     else ("blocks", layer - n_dense))
        wkv_b = p[stack]["attn"]["wkv_b"][at]
        t0 = time.perf_counter()
        got = kernel(pool, wkv_b, jnp.int32(layer)).astype(jnp.float32)
        got.block_until_ready()
        took.append(time.perf_counter() - t0)
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
            # np.maximum, not max(): a NaN reading has to come out NaN
            if scale_dim is None:
                worst = float(np.maximum(worst, err))
            else:
                control = float(np.maximum(control, err))
    return {"latent_kernel_rel_err": worst,
            "latent_kernel_control": control,
            "latent_kernel_dead_rows_zero": int(zero),
            "latent_kernel_live_rows": int(live.sum()),
            "latent_kernel_us": 1e6 * min(took[1:] or took)}


class LatentBenchLLMServer(HybridBenchLLMServer):

    def bench_reference(self, samples, config: dict) -> list:
        """Each sample's tokens (prompt, then what the engine streamed)
        through the engine's own model on the chip, against the module
        the configuration names, with the engine's own (served) weights;
        then the absorbed decode on the pages that request left.  The
        engine is idle: every request of the window has finished."""
        import jax.numpy as jnp
        reference = importlib.import_module(config["program"]["reference"])
        eng = self.engine
        took, t0 = {}, time.perf_counter()

        def lap(name):
            nonlocal t0
            now = time.perf_counter()
            took[name], t0 = round(now - t0, 2), now
        weights = reference.from_program_params(eng.params)
        # every request's reference at the longest one's length: one
        # compiled layer a kind, not one a request
        longest = max((len(s["prompt"]) + len(s["tokens"]) for s in samples),
                      default=0)
        out = []
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            seq = (list(s["prompt"]) + list(s["tokens"]))[:-1]
            got = program_hidden(eng, seq, n)
            m = {"context": n + len(s["tokens"]), "bucket": got["bucket"],
                 "served_token_agree_share": served_token_agreement(
                     eng, got["hidden"][n - 1:], s["tokens"])}
            lap(f"{i}.program")
            m.update(latent_kernel_check(eng, reference, got["pages"],
                                         len(seq)))
            lap(f"{i}.kernel")
            m.update(reference.router_check(
                weights, got["router_in"], got["router_out"]))
            m.update(reference.hidden_check(
                weights, jnp.asarray(seq), got["hidden"], config,
                faults=s.get("faults", reference.FAULTS), pad_to=longest))
            lap(f"{i}.reference")
            out.append(m)
        if out:
            out[-1]["took_s"] = took
        return out

    def device_info(self) -> dict:
        """``LLMServer.device_info`` and which implementation the expert
        layer's decode step resolves to here."""
        from ray_tpu.ops.moe import expert_kernel_applies
        from ray_tpu.serve.llm import LLMServer
        eng = self.engine
        cfg = eng.cfg
        return {**LLMServer.device_info(self),
                "moe_impl": "tpu" if expert_kernel_applies(
                    eng._rows * cfg.moe_top_k, cfg.d_model, cfg.moe_d_ff)
                else "xla"}
