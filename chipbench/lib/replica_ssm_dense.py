"""``BenchLLMServer`` for a configuration whose state-space layers carry a
dense feed-forward each and whose equations carry published scalar
multipliers (``chipbench/README-ssm-dense.md``).  The base is
``lib/replica_ssm.py SsmBenchLLMServer``: the serving path, the trace,
the facts, the one-program weights and the warm-up are inherited
untouched, and so are two of its checks on GIVEN inputs
(``ssm_kernel_check``, ``ssm_prefill_check``: they count the layers of
class ``"mamba2"``, so they are handed a view of the engine whose
configuration counts this model's Mamba-2 layers under that name).  What
differs is what ``replica_ssm``'s own check cannot do here: it captures
a router's inputs and logits in every expert layer (this model has
none: its ``_router_io`` joins an empty list), compares no logits (so
nothing would hold the logits' divisor or the tied, unmultiplied table),
and runs the paged kernel and its plain counterpart at ``head_dim^-1/2``
(this model states 1/64 for heads of 64).

``program_hidden`` runs a finished request's tokens through the engine's
own model, weights, pool, state entries and page tables: the prompt by
the paged prefill path at the engine's bucket with its real length,
padded as the engine pads it; every later position as one decode step in
the engine's decode shape (``ssm_decode`` and ``paged_attention_decode``
and all), the request in row 0 on state entry 1.  It returns the hidden
states and what the request's entry holds afterwards; ``program_logits``
puts ``LOGIT_ROWS`` of them through the engine's own head
(``models/gpt.py output_logits``: the tied table, the divisor); the
reference module holds both to float32 and to references made wrong on
purpose, five of them in ONE published scalar each.
"""

import functools
import importlib
import time

from chipbench.lib.replica_arch import served_token_agreement
from chipbench.lib.replica_hybrid import DECODE_CHUNK
from chipbench.lib.replica_ssm import (SsmBenchLLMServer, ssm_kernel_check,
                                       ssm_prefill_check)

LOGIT_ROWS = 64           # positions of a sample whose logits are compared
KERNEL_STEPS = 128        # of ``ssm_kernel_check`` (256 in ``serve_ssm``)


class _View:
    """``inner`` with some attributes replaced; everything else is
    ``inner``'s own (an engine's ``_cache`` is the engine's own dict: what
    a check writes there, the engine holds)."""

    def __init__(self, inner, **replaced):
        self.__dict__.update(replaced, _inner=inner)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _counted_as_mamba2(eng):
    """A view of the engine whose configuration answers
    ``layers_of("mamba2")`` with the ``"mamba2_mlp"`` layers too:
    ``replica_ssm``'s kernel checks ask for the LAST Mamba-2 layer's
    index by that name, and this model's recurrent leaves are the same
    leaves under another block class."""
    cfg = eng.cfg

    def layers_of(*kinds):
        return cfg.layers_of(*kinds, *(("mamba2_mlp",)
                                       if "mamba2" in kinds else ()))
    return _View(eng, cfg=_View(cfg, layers_of=layers_of))


def _apply(model, params, cache, toks, poss, tabs, **recurrent):
    out, mut = model.apply(
        {"params": params, "cache": cache}, toks, poss, block_tables=tabs,
        return_hidden=True, mutable=["cache"], **recurrent)
    return out, mut["cache"]


def _prefill_of(eng, bucket: int):
    """``fn(params, cache, toks [1, bucket], table [1, pages], n [1]) ->
    (cache, hidden [bucket, d])``: one prompt of real length ``n``
    through the paged prefill path on state entry 1.  One jitted
    function an engine and bucket, so that a second request of the
    bucket finds the first one's program."""
    import jax
    import jax.numpy as jnp
    made = eng.__dict__.setdefault("_bench_prefill", {})
    if bucket not in made:
        model = eng.model

        @functools.partial(jax.jit, donate_argnums=(1,))
        def fn(params, cache, toks, table, n):
            out, cache = _apply(
                model, params, cache, toks, jnp.arange(bucket)[None], table,
                lengths=n, state_rows=jnp.asarray([1], jnp.int32))
            return cache, out[0]
        made[bucket] = fn
    return made[bucket]


def _decode_chunk_of(eng):
    """``fn(params, cache, toks [K], poss [K], steps [K], tables, entries)
    -> (cache, hidden [K, d])``: one decode step a token in the engine's
    decode shape, the request in row 0; a step ``steps`` leaves out runs
    with every row dead (its tables zeroed), so it moves no state."""
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
                out, cache = _apply(
                    model, params, cache, rows.at[0, 0].set(tok),
                    rows.at[0, 0].set(pos), jnp.where(step, tables, 0),
                    state_rows=jnp.where(step, entries, 0))
                return cache, out[0, 0]
            return jax.lax.scan(one, cache, (toks, poss, steps))
        eng._bench_decode_chunk = fn
    return fn


def program_hidden(eng, tokens, n_prompt: int) -> dict:
    """``tokens [S]`` through the engine's model on the engine's pool and
    state entries (pages 1.., entry 1 of an idle engine): positions ``<
    n_prompt`` in one paged prefill at the engine's bucket, told the real
    length; the others one decode step each, ``DECODE_CHUNK`` steps a
    call, the request in row 0 of the engine's rows.  ``hidden [S, d]``
    is post-final-norm, in the model's dtype; ``state [L, N, H*P]``,
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
    eng._cache, hid = _prefill_of(eng, bucket)(
        eng.params, eng._cache, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray([n_prompt], jnp.int32))
    hid = [hid[:n_prompt]]
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
        eng._cache, more = _decode_chunk_of(eng)(
            eng.params, eng._cache, jnp.asarray(toks), jnp.asarray(at),
            jnp.asarray(steps), jnp.asarray(tables), jnp.asarray(entries))
        hid.append(more[:real])
    return {"hidden": jnp.concatenate(hid), "bucket": bucket,
            "state": eng._cache["ssm_state"][:, 1],
            "tail": eng._cache["ssm_conv"][:, 1], "pages": table[0, :used]}


def program_logits(eng, hidden, n_prompt: int):
    """``(rows [R], float32 logits [R, V])``: the engine's own head
    (``output_logits``: the stored table, the divisor) on ``LOGIT_ROWS``
    positions of ``hidden [S, d]`` spread over the sequence, the
    prompt's last (whose logits gave the first token) among them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.gpt import output_logits
    rows = np.unique(np.append(np.linspace(
        0, hidden.shape[0] - 1, LOGIT_ROWS).astype(np.int64), n_prompt - 1))
    got = jax.jit(lambda p, x: output_logits(eng.cfg, p, x))(
        eng.params, hidden[jnp.asarray(rows)])
    return rows, got


def paged_kernel_check(eng, reference, pages, context: int) -> dict:
    """The paged decode kernel (32 query heads on 8 KV heads) at the
    model's STATED softmax scale on the pages a scored request of
    ``context`` positions left in the pool's first layer, in the engine's
    decode shape: every row reads the same pages, at lengths from one
    position to the whole context.  ``paged_kernel_rel_err`` is the
    largest row's |kernel - plain| / |plain| against
    ``reference.decode_attention`` over the same keys at that scale;
    ``paged_kernel_control`` the same against plain attention that
    misses each row's newest key; ``paged_kernel_sqrt_scale_control``
    against plain attention at ``head_dim^-1/2`` (what a kernel that was
    not handed the scale computes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import paged_attention
    cfg = eng.cfg
    pool = next(a for a in jax.tree.leaves(eng._cache)
                if eng._is_pool_leaf(a))
    rows, hd = eng._rows, cfg.head_dim
    scale = cfg.attention_multiplier
    lengths = np.linspace(2, context, rows).astype(np.int32)
    tables = np.zeros((rows, eng.max_pages), np.int32)
    tables[:, :len(pages)] = pages
    # queries of the size the model's own are (|q| ~ 8 a head of 64):
    # at 1/64 unit-normal queries would leave every softmax flat
    q = (jax.random.normal(jax.random.PRNGKey(0), (rows, cfg.n_heads, hd),
                           jnp.float32) * hd ** 0.5).astype(cfg.dtype)
    got = jax.jit(lambda pool: paged_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(lengths), layer=0,
        sm_scale=scale))(pool).astype(jnp.float32)
    kv = pool[0, jnp.asarray(pages)]                # [n, KV, page, 2 hd]
    kv = jnp.moveaxis(kv, 1, 2).reshape(-1, kv.shape[1], 2 * hd)
    out = {}
    for name, lens, s in (
            ("paged_kernel_rel_err", lengths, scale),
            ("paged_kernel_control", lengths - 1, scale),
            ("paged_kernel_sqrt_scale_control", lengths, hd ** -0.5)):
        want = reference.decode_attention(q, kv[..., :hd], kv[..., hd:],
                                          jnp.asarray(lens), s)
        out[name] = float(jnp.max(
            jnp.linalg.norm((got - want).reshape(rows, -1), axis=-1)
            / jnp.linalg.norm(want.reshape(rows, -1), axis=-1)))
    return out


class DenseSsmBenchLLMServer(SsmBenchLLMServer):

    def bench_reset_peaks(self) -> bool:
        """Start the engine's two high-water marks again (before the
        window); False where the program has none."""
        reset = getattr(self.engine.stats, "reset_peaks", None)
        if reset is not None:
            reset()
        return reset is not None

    def bench_reference(self, samples, config: dict) -> list:
        """Each sample's tokens (prompt, then what the engine streamed)
        through the engine's own model on the chip, against the module
        the configuration names, with the engine's own (served) weights:
        hidden states, logits, the state and tail its entry was left
        with, the paged kernel on its pages; and once, with the last
        sample, the state-space decode kernel and the chunked prompt
        form on given inputs.  The engine is idle: every request of the
        window has finished."""
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
        # the kernels first: program_hidden overwrites entry 1
        view = _counted_as_mamba2(eng)
        kernel = ssm_kernel_check(view, reference, KERNEL_STEPS)
        kernel.update(ssm_prefill_check(view, reference))
        lap("kernel")
        out = []
        # each request at its own length (a multiple of the reference's
        # PAD): the short one's nine forward passes at 256 positions cost
        # a fifth of what they cost at the long one's 1,280, and the
        # compile cache keeps both sets of the reference's programs
        layers = cfg.layers_of("mamba2_mlp")
        for s, which in zip(samples, ("first", "second")):
            n = len(s["prompt"])
            seq = (list(s["prompt"]) + list(s["tokens"]))[:-1]
            got = program_hidden(eng, seq, n)
            rows, got_logits = program_logits(eng, got["hidden"], n)
            m = {"context": n + len(s["tokens"]), "bucket": got["bucket"],
                 "mamba_layers": layers,
                 "served_token_agree_share": served_token_agreement(
                     eng, got["hidden"][n - 1:], s["tokens"])}
            lap(which + ".program")
            left = [reference.from_program_state(
                got["state"][layer], got["tail"][layer], cfg.mamba_heads,
                cfg.mamba_conv_kernel) for layer in range(layers)]
            m.update(reference.hidden_check(
                weights, jnp.asarray(seq), got["hidden"], config,
                n_prompt=n, bucket=got["bucket"], left=left,
                got_logits=got_logits,
                logit_rows=jnp.asarray(rows)))
            lap(which + ".reference")
            m.update(paged_kernel_check(eng, reference, got["pages"],
                                        len(seq)))
            lap(which + ".paged")
            out.append(m)
        if out:
            out[-1].update(kernel, took_s=took)
        return out

    def device_info(self) -> dict:
        """``LLMServer.device_info`` and which implementation the
        state-space decode step resolves to here."""
        from ray_tpu.ops.mamba2 import resolve_ssm_impl
        from ray_tpu.serve.llm import LLMServer
        cfg = self.engine.cfg
        # past SsmBenchLLMServer's, which asks after the experts' kernel
        return {**LLMServer.device_info(self),
                "ssm_impl": resolve_ssm_impl(
                    cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups)}
