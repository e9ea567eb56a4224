"""Model configuration presets.

Sizes chosen to line up with the reference's benchmark families
(BASELINE.md: GPT-J-6B pretraining is the Train north-star; resnet50 the
vision baseline) plus tiny configs for tests.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


# block classes of ``TransformerConfig.layer_types`` by what a layer
# keeps of a sequence: KV pages in the paged pool, or a fixed-size
# recurrent state entry a request; and those that are one mixer a layer
POOL_KINDS = ("full_attention", "attention_only")
STATE_KINDS = ("linear_attention", "kda", "mamba2", "mamba2_mlp")
# of those, the classes whose recurrence is Mamba-2's: their state leaves
# are ``ssm_state`` / ``ssm_conv`` (models/gpt.py GPT._stack_periods)
MAMBA_KINDS = ("mamba2", "mamba2_mlp")
MIXER_KINDS = ("mamba2", "latent_moe", "attention_only")
# ``TransformerConfig.attention_impl`` (ops/attention.py says what each is)
ATTENTION_IMPLS = ("auto", "xla", "flash", "ring", "ulysses")


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None       # None -> = n_heads (MHA)
    d_ff: Optional[int] = None             # None -> 4 * d_model (8/3 for swiglu)
    max_seq_len: int = 2048
    rope_theta: Optional[float] = 10000.0  # None: no layer rotates
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16        # activation dtype
    param_dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"           # one of ATTENTION_IMPLS
    remat: bool = True                     # checkpoint each block (HBM <-> FLOPs)
    remat_policy: str = "dots"             # "dots": save no-batch-dim dots
    # (cheap recompute, more HBM); "nothing": full per-block recompute —
    # the memory-lean setting that fits ~1B params on one 16 GiB chip
    scan_layers: bool = True               # lax.scan over layers
    tie_embeddings: bool = False
    z_loss: float = 1e-4
    # Mixture-of-experts (0 -> dense MLP).  Experts shard over the mesh's
    # data axes (expert parallelism, ray_tpu/ops/moe.py).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # None -> d_model // n_heads; a model whose heads * head_dim differs
    # from d_model states it (wq/wk/wv map d_model -> heads * head_dim,
    # wo maps it back)
    head_dim: Optional[int] = None
    moe_d_ff: Optional[int] = None         # an expert's width; None -> d_ff
    moe_act: str = "silu"                  # silu (SwiGLU) | relu (ReGLU)
    # dropless: every (token, chosen expert) pair is computed whatever
    # the imbalance (ops/moe.py DroplessMoE), no capacity, no aux loss
    moe_dropless: bool = False
    # the router reads the block's normalised ATTENTION input, not the
    # expert layer's own input
    moe_router_pre_attn: bool = False
    # Per-layer attention kinds, looked up by layer index (layer l uses
    # entry l; the layouts may be longer than n_layers: a model cut in
    # depth keeps its published lists).  rope_layout[l] == 0 -> the layer
    # does not rotate (NoPE); window_layout[l] == 1 -> key j is visible
    # to query i only while j > i - sliding_window.  None -> every layer
    # rotates / windows iff sliding_window is set.
    sliding_window: Optional[int] = None
    rope_layout: Optional[tuple] = None
    window_layout: Optional[tuple] = None
    # Per-layer BLOCK classes ("full_attention" | "linear_attention" |
    # "kda" | "mamba2_mlp", and the single-mixer classes further down; layer l
    # is entry l, the tuple may be longer than n_layers).  Where
    # it names more than one class the layer stack scans one PERIOD of
    # it (``period``; models/gpt.py Period).  A linear_attention layer is
    # a Gated DeltaNet mixer
    # (ops/gated_delta.py): linear_key_heads x linear_key_head_dim
    # queries and keys, linear_value_heads x linear_value_head_dim
    # values and gates, a causal depthwise convolution of
    # linear_conv_kernel taps in front; linear_allow_neg_eigval: the
    # write strength ranges over (0, 2), not (0, 1).  It holds no KV
    # pages but a fixed-size state a request (serve/llm_engine.py).
    # A kda layer (models/gpt.py KimiDeltaAttention, the kimi_linear
    # family's) is the same rule with a decay a KEY CHANNEL, from a
    # low-rank pair of linear_gate_rank, a sigmoid output gate from
    # another such pair, and a write strength in (0, 1); its
    # feed-forward is what a full_attention layer's is at that depth
    # (first_dense_layers, the moe_* experts), and the full_attention
    # layers beside it may be latent (kv_lora_rank).
    # A mamba2_mlp layer (models/gpt.py MambaBlock) is the same pre-norm
    # block around a Mamba-2 mixer (the mamba_* sizes further down) and
    # a dense SwiGLU of d_ff: the granitemoehybrid family's layer.
    # layer_period (stated, not found: a pattern cut short reads as
    # aperiodic): the stack is the whole periods of that many layers
    # that a leading dense layer breaks, unrolled (``runs``: the head
    # run), then a SCAN of the whole periods behind them, then the
    # partial period that is left, unrolled (the tail run).
    layer_types: Optional[tuple] = None
    layer_period: Optional[int] = None
    linear_key_heads: Optional[int] = None
    linear_value_heads: Optional[int] = None
    linear_key_head_dim: Optional[int] = None
    linear_value_head_dim: Optional[int] = None
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = False
    linear_gate_rank: Optional[int] = None
    # RMSNorm of q and k before the rotation, in one of two forms: over
    # the WHOLE projection, heads unsplit (one weight of heads * head_dim:
    # OLMo 2/3), or with qk_norm_per_head over each head's head_dim alone
    # (one weight of head_dim, shared by the heads: EXAONE 4.0, K-EXAONE)
    qk_norm: bool = False
    qk_norm_per_head: bool = False
    # a full_attention block normalises each sub-layer's OUTPUT (x +
    # Norm(f(x)), the OLMo 2/3 block), not its input
    post_norm: bool = False
    # Latent attention (MLA; models/gpt.py LatentAttention), on where
    # kv_lora_rank is set: a token's cache row in a layer is ONE vector
    # [c (kv_lora_rank, normed) | k_rope (qk_rope_head_dim, rotated)]
    # shared by every head; a head's query is [nope | rope] of
    # qk_nope_head_dim + qk_rope_head_dim (= head_dim, the softmax
    # scale's), its value v_head_dim.  rope_interleave: the rotation
    # pairs dims (2j, 2j+1), not (j, j + half).
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_interleave: bool = False
    # The dropless router's scoring (ops/moe.py route_top_k): "softmax"
    # over the chosen logits, or "sigmoid": scores sigmoid(logits), the
    # choice by score + a learned selection bias, the gates the chosen
    # scores alone, renormalised, times moe_route_scale.
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    # shared experts: one SwiGLU of moe_shared_experts * moe_d_ff beside
    # the routed sum, every token through it
    moe_shared_experts: int = 0
    # the first first_dense_layers layers have a dense SwiGLU of d_ff
    # where the others have experts (a prefix stack ahead of the scanned
    # one, models/gpt.py GPT)
    first_dense_layers: int = 0
    # expert parallelism as ONE chip sees it: the router scores all
    # moe_experts, this program holds moe_experts_held of them starting
    # at moe_held_first and computes the pairs that chose those; what the
    # absent experts would add is some other chip's to compute.  None:
    # every expert is held
    moe_experts_held: Optional[int] = None
    moe_held_first: int = 0
    # Layers that are ONE mixer each, x + Mixer(Norm(x)) (models/gpt.py
    # MixerBlock), three more entries of layer_types:
    #   "mamba2": a Mamba-2 mixer (ops/mamba2.py): mamba_heads x
    #     mamba_head_dim channels, B and C of ssm_state_size shared by
    #     the heads of each of mamba_groups groups, a causal depthwise
    #     convolution of mamba_conv_kernel taps with a bias over [x; B;
    #     C], prompts in chunks of mamba_chunk.  A fixed-size state a
    #     request, as a linear_attention layer's;
    #   "latent_moe": routed experts in a latent of moe_latent_size
    #     around the moe_* router, with one shared expert of
    #     moe_shared_d_ff on the full width (ops/moe.py LatentMoE);
    #   "attention_only": the Attention of a full_attention layer and
    #     nothing else; it holds KV pages.
    # A latent_moe layer's experts are NOT gated, down(act(up x)), two
    # matrices each; moe_act "relu2" is relu(.)^2.
    mamba_heads: Optional[int] = None
    mamba_head_dim: Optional[int] = None
    ssm_state_size: Optional[int] = None
    mamba_groups: int = 1
    mamba_conv_kernel: int = 4
    mamba_chunk: int = 128
    moe_latent_size: Optional[int] = None
    moe_shared_d_ff: Optional[int] = None
    # multi-token-prediction modules behind the stack (DeepSeek-V3's MTP;
    # models/gpt.py MTPModule), 0 or 1: one more block (global attention
    # without rotation, experts where the stack has them) that reads the
    # stack's last hidden state at i beside the embedding of token i + 1
    # and predicts token i + 2 through the model's own head.  It holds KV
    # pages as a layer of the stack does (the pool's last layer), and the
    # serving engine drafts with it (serve/llm_engine.py)
    mtp_layers: int = 0
    # Four published scalars of a model trained under muP (the granite
    # family's), none folded into a weight: the embedding's output times
    # embedding_multiplier; each residual branch's output (mixer and
    # feed-forward, in full_attention and mamba2_mlp layers) times
    # residual_multiplier; the softmax scale attention_multiplier where
    # it is stated (None: head_dim^-1/2); the logits divided by
    # logits_scaling (a tied head reads the UNmultiplied table).  At
    # their defaults the program is what it was without them
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.n_kv_heads is None:
            self.n_kv_heads = self.n_heads
        if self.d_ff is None:
            self.d_ff = 4 * self.d_model
        if self.head_dim is None:
            assert self.d_model % self.n_heads == 0
            self.head_dim = self.d_model // self.n_heads
        if self.moe_d_ff is None:
            self.moe_d_ff = self.d_ff
        assert self.n_heads % self.n_kv_heads == 0
        if self.attention_impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"attention_impl={self.attention_impl!r}: must be one of "
                + " | ".join(ATTENTION_IMPLS))
        assert self.moe_act in ("silu", "relu", "relu2")
        assert self.moe_scoring in ("softmax", "sigmoid")
        if self.kv_lora_rank:
            assert self.head_dim == (self.qk_nope_head_dim
                                     + self.qk_rope_head_dim)
            assert self.v_head_dim and not self.qk_norm
            assert not self.layers_differ and not self.sliding_window
            # beside recurrent layers the latent layers are kda's
            assert set(self.layer_types or ()) <= {"full_attention", "kda"}
        if self.moe_experts_held is not None:
            assert self.moe_dropless and 0 <= self.moe_held_first \
                <= self.moe_experts - self.moe_experts_held
        assert 0 <= self.first_dense_layers <= self.n_layers
        assert self.qk_norm or not self.qk_norm_per_head
        assert self.mtp_layers in (0, 1)
        if self.mtp_layers:     # a block of the stack's own class
            assert self.layer_types is None and not self.kv_lora_rank
        for layout in (self.rope_layout, self.window_layout):
            assert layout is None or len(layout) >= self.n_layers
        if self.rope_layout is not None:
            self.rope_layout = tuple(int(v) for v in self.rope_layout)
        if self.window_layout is not None:
            assert self.sliding_window
            self.window_layout = tuple(int(v) for v in self.window_layout)
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            assert len(self.layer_types) >= self.n_layers
            assert set(self.layer_types) <= set(POOL_KINDS + STATE_KINDS
                                                + ("latent_moe",))
            assert not self.layers_differ
            kinds = set(self.layer_types[:self.n_layers])
            if "kda" in kinds:
                # its feed-forward is Block's: dense, or dropless experts
                assert kinds <= {"kda", "full_attention"}
                assert self.linear_gate_rank and not self.post_norm
                assert self.linear_key_heads == self.linear_value_heads
                assert not self.moe_experts or self.moe_dropless
            else:
                # experts live in latent_moe layers or in none of a period
                assert bool(self.moe_experts) == ("latent_moe" in kinds)
                assert not self.first_dense_layers
                if self.moe_experts:
                    assert self.moe_dropless and self.moe_latent_size \
                        and self.moe_shared_d_ff
            if self.layer_period:
                head, periods, _ = self.runs
                scanned = self.layer_types[head:head
                                           + periods * self.layer_period]
                assert periods and scanned == self.period * periods
            if set(MAMBA_KINDS) & set(self.layer_types):
                # one recurrent class a model: its leaves have one shape
                assert len(set(STATE_KINDS) & set(self.layer_types)) == 1
                assert self.mamba_heads % self.mamba_groups == 0
        if self.attention_multiplier is not None:
            assert not self.kv_lora_rank    # LatentAttention states its own

    @property
    def period(self) -> Optional[tuple]:
        """The shortest run of block classes that the first ``n_layers``
        of ``layer_types`` are whole repeats of, where they name more
        than one class (the layer stack then scans that run; a pattern
        that is not periodic is one run of all its layers); else None."""
        kinds = (self.layer_types or ())[:self.n_layers]
        if len(set(kinds)) < 2:
            return None
        if self.layer_period:       # the first whole one behind the head
            head = self.runs[0]
            return kinds[head:head + self.layer_period]
        return next(kinds[:p] for p in range(1, len(kinds) + 1)
                    if len(kinds) % p == 0
                    and all(k == kinds[i % p] for i, k in enumerate(kinds)))

    @property
    def runs(self) -> tuple:
        """``(head, periods, tail)``: the layers of the unrolled head
        run, the scanned whole periods behind it, and the layers of the
        unrolled tail run (``layer_period``; without it the stack is
        whole periods and nothing else)."""
        p = self.layer_period
        if not p:
            return 0, self.n_layers // len(self.period or (None,)), 0
        head = -(-self.first_dense_layers // p) * p
        periods = (self.n_layers - head) // p
        return head, periods, self.n_layers - head - periods * p

    def layers_of(self, *kinds: str) -> int:
        """How many of the ``n_layers`` are of one of the block classes
        ``kinds`` (every layer is full_attention without
        ``layer_types``)."""
        if self.layer_types is None:
            return self.n_layers if "full_attention" in kinds else 0
        return sum(k in kinds for k in self.layer_types[:self.n_layers])

    @property
    def layers_differ(self) -> bool:
        """Some layer's attention is of another kind than the others':
        the layer stack then hands every block its layer index."""
        return self.rope_layout is not None or self.window_layout is not None

    @property
    def rope_dim(self) -> int:
        """How many dims of a head the rotation turns."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def cache_row_width(self) -> int:
        """The paged pool's minor dimension, a token's row in a layer and
        a KV head: ``[k | v]`` of ``2*head_dim``, or the latent row ``[c
        | k_rope]`` padded with zeros to whole lane tiles of 128 (576 ->
        640: ops/paged_attention.py layout note)."""
        if not self.kv_lora_rank:
            return 2 * self.head_dim
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def cache_kv_heads(self) -> int:
        """KV heads of the paged pool: one where the row is latent."""
        return 1 if self.kv_lora_rank else self.n_kv_heads

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this program holds."""
        return (self.moe_experts if self.moe_experts_held is None
                else self.moe_experts_held)

    def _attn_params(self) -> int:
        if self.kv_lora_rank:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            return (self.d_model * self.n_heads * self.head_dim
                    + self.d_model * (r + rope) + r
                    + r * self.n_heads * (self.qk_nope_head_dim
                                          + self.v_head_dim)
                    + self.n_heads * self.v_head_dim * self.d_model)
        qk = (0 if not self.qk_norm else 2 * self.head_dim
              if self.qk_norm_per_head
              else (self.n_heads + self.n_kv_heads) * self.head_dim)
        return self.d_model * self.head_dim * (
            self.n_heads * 2 + self.n_kv_heads * 2) + qk

    def _linear_attn_params(self) -> int:
        """A Gated DeltaNet mixer: q, k, v, gate and output
        projections, the two per-head gates' projections, the
        convolution's taps, ``A_log`` and ``dt_bias`` a value head, the
        output norm's one weight of ``linear_value_head_dim``."""
        qk = self.linear_key_heads * self.linear_key_head_dim
        vv = self.linear_value_heads * self.linear_value_head_dim
        return (self.d_model * (2 * qk + 2 * vv) + vv * self.d_model
                + 2 * self.d_model * self.linear_value_heads
                + self.linear_conv_kernel * (2 * qk + vv)
                + 2 * self.linear_value_heads + self.linear_value_head_dim)

    def _kda_params(self) -> int:
        """A Kimi Delta Attention mixer: q, k, v and output projections,
        the decay's and the output gate's low-rank pairs, the write
        strength's projection, the three convolutions' taps, ``A_log`` a
        head, ``dt_bias`` a key channel, the output norm's one weight
        of ``linear_value_head_dim``."""
        d, rank = self.d_model, self.linear_gate_rank
        qk = self.linear_key_heads * self.linear_key_head_dim
        vv = self.linear_value_heads * self.linear_value_head_dim
        return (d * (2 * qk + vv) + vv * d
                + rank * (2 * d + qk + vv) + d * self.linear_value_heads
                + self.linear_conv_kernel * (2 * qk + vv)
                + self.linear_value_heads + qk + self.linear_value_head_dim)

    def _mixer_params(self, kind: str) -> int:
        """One single-mixer layer of class ``kind`` with its norm.
        mamba2: the input projection to ``[z | x B C | dt]``, the output
        projection, the convolution's taps and bias, ``A_log``,
        ``dt_bias`` and ``D`` a head, the gated norm's weight.
        latent_moe: router and its selection bias, the latent's two
        projections, the shared expert, and the ``experts_here`` routed
        experts, two matrices each (none of them is gated)."""
        d = self.d_model
        if kind == "attention_only":
            return self._attn_params() + d
        if kind == "mamba2":
            inner = self.mamba_heads * self.mamba_head_dim
            conv = inner + 2 * self.mamba_groups * self.ssm_state_size
            return (d * (inner + conv + self.mamba_heads) + inner * d
                    + (self.mamba_conv_kernel + 1) * conv
                    + 3 * self.mamba_heads + inner + d)
        return (d * self.moe_experts
                + (self.moe_experts if self.moe_scoring == "sigmoid" else 0)
                + 2 * d * self.moe_latent_size
                + 2 * d * self.moe_shared_d_ff
                + self.experts_here * 2 * self.moe_latent_size
                * self.moe_d_ff + d)

    def layer_params(self, kind: str = "full_attention") -> int:
        """One layer of class ``kind``: a dense block (mixer, SwiGLU,
        two norms) or a single mixer with its norm."""
        if kind in MIXER_KINDS:
            return self._mixer_params(kind)
        mixer = (self._attn_params() if kind == "full_attention"
                 else self._linear_attn_params() if kind == "linear_attention"
                 else self._kda_params() if kind == "kda"
                 # the single-mixer layer's count, less its one norm
                 else self._mixer_params("mamba2") - self.d_model)
        return mixer + 3 * self.d_model * self.d_ff + 2 * self.d_model

    def num_params(self) -> int:
        """Parameters this program holds (``experts_here`` routed experts
        a layer, not all the router scores)."""
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        if self.layers_of(*MIXER_KINDS, "mamba2_mlp"):
            return emb + self.d_model + sum(
                self.layer_params(k)
                for k in self.layer_types[:self.n_layers])
        dense = 3 * self.d_model * self.d_ff
        if self.moe_experts:
            mlp = (self.experts_here + self.moe_shared_experts) * 3 \
                * self.d_model * self.moe_d_ff \
                + self.d_model * self.moe_experts            # + router
            if self.moe_scoring == "sigmoid":
                mlp += self.moe_experts                      # + its bias
        else:
            mlp = dense
        norms = 2 * self.d_model
        linear, kda = self.layers_of("linear_attention"), self.layers_of("kda")
        mixers = (self.n_layers - linear - kda) * self._attn_params() + (
            linear and linear * self._linear_attn_params()) + (
            kda and kda * self._kda_params())
        first = self.first_dense_layers
        # the module: two input norms, their projection, a block, a norm
        mtp = self.mtp_layers * (
            2 * self.d_model * self.d_model + 3 * self.d_model
            + self._attn_params() + mlp + norms)
        return (emb + mixers + first * dense + (self.n_layers - first) * mlp
                + self.n_layers * norms + self.d_model + mtp)

    def train_flops_per_token(self, seq_len: int) -> float:
        """Operations a training step REQUIRES per token — the count
        model-FLOPs utilization is taken over (the goodput ledger's
        ``mfu``; ``chipbench/lib/flops.py`` counts the same way from the
        published sizes, and a test pins the two together).  Counted:
        every matrix multiplication of the blocks and the output head,
        forward and backward (6 per parameter per token; an MoE block
        runs ``moe_top_k`` experts a token), and causal attention once
        (``QK^T`` and ``PV``: the masked half is not work the algorithm
        needs).  Not counted: the embedding gather, recomputation under
        remat, norms, rotary and softmax elementwise work.  A window
        is not taken off the attention term: it is an upper bound there."""
        attn = self._attn_params()
        dense = 3 * self.d_model * self.d_ff
        if self.moe_experts:
            mlp = 3 * self.d_model * self.moe_d_ff * (
                self.moe_top_k + self.moe_shared_experts) \
                + self.d_model * self.moe_experts            # + router
        else:
            mlp = dense
        first = self.first_dense_layers
        head = self.vocab_size * self.d_model
        # QK^T over head_dim and PV over the value's (v_head_dim where
        # they differ), forward and backward, the causal half
        attention = 3 * seq_len * self.n_heads * (
            self.head_dim + (self.v_head_dim or self.head_dim)) \
            * self.n_layers
        return 6.0 * (self.n_layers * attn + first * dense
                      + (self.n_layers - first) * mlp + head) + attention


def pattern_layer_types(pattern: str) -> tuple:
    """``layer_types`` of a ``hybrid_override_pattern`` (the nemotron_h
    family publishes its layers as a string, a letter a layer)."""
    return tuple({"M": "mamba2", "E": "latent_moe",
                  "*": "attention_only"}[letter] for letter in pattern)


PRESETS = {
    # test-size
    "tiny": TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                              n_heads=4, d_ff=128, max_seq_len=128,
                              dtype=jnp.float32, remat=False),
    # test-size MoE (8 experts, top-2)
    "tiny-moe": TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=4, d_ff=128, max_seq_len=128,
                                  dtype=jnp.float32, remat=False,
                                  moe_experts=8, moe_top_k=2),
    # Mixtral-8x7B shapes (headline open MoE family)
    "mixtral-8x7b": TransformerConfig(vocab_size=32000, d_model=4096,
                                      n_layers=32, n_heads=32, n_kv_heads=8,
                                      d_ff=14336, max_seq_len=8192,
                                      rope_theta=1e6, moe_experts=8,
                                      moe_top_k=2),
    # ~124M GPT-2 small shapes
    "gpt-small": TransformerConfig(vocab_size=50304, d_model=768, n_layers=12,
                                   n_heads=12, max_seq_len=1024),
    # ~350M GPT-2 medium shapes (largest config whose fp32 AdamW states +
    # remat activations fit one 16 GiB v5e chip with headroom)
    "gpt-medium": TransformerConfig(vocab_size=50304, d_model=1024,
                                    n_layers=24, n_heads=16,
                                    max_seq_len=1024),
    # GPT-2 large shapes — ~1.07B params with the SwiGLU MLP (needs the
    # memory-lean path on a single 16 GiB chip: full remat + chunked CE +
    # adafactor; comfortable under fsdp on 2+)
    "gpt-large": TransformerConfig(vocab_size=50304, d_model=1280,
                                   n_layers=36, n_heads=20,
                                   max_seq_len=1024),
    # ~1.3B
    "gpt-xl": TransformerConfig(vocab_size=50304, d_model=2048, n_layers=24,
                                n_heads=16, max_seq_len=2048),
    # GPT-J-6B shapes (the reference Train benchmark model, BASELINE.md)
    "gptj-6b": TransformerConfig(vocab_size=50400, d_model=4096, n_layers=28,
                                 n_heads=16, max_seq_len=2048),
    # LLaMA-3-8B shapes (the reference Serve benchmark model, BASELINE.md)
    "llama3-8b": TransformerConfig(vocab_size=128256, d_model=4096,
                                   n_layers=32, n_heads=32, n_kv_heads=8,
                                   d_ff=14336, max_seq_len=8192,
                                   rope_theta=500000.0),
    # SmallThinker-21BA3B-Instruct (PowerInfer) as published: 52 expert
    # layers of 64 ReGLU experts (width 768), top-6, dropless, routed
    # from the attention's input; heads * head_dim = 3584 != d_model; a
    # 4-layer period of one global layer without positions and three
    # rotating layers with a window of 4096
    "smallthinker-21b-a3b": TransformerConfig(
        vocab_size=151936, d_model=2560, n_layers=52, n_heads=28,
        n_kv_heads=4, head_dim=128, d_ff=768, max_seq_len=16384,
        rope_theta=1.5e6, norm_eps=1e-6, moe_experts=64, moe_top_k=6,
        moe_d_ff=768, moe_act="relu", moe_dropless=True,
        moe_router_pre_attn=True, sliding_window=4096,
        rope_layout=(0, 1, 1, 1) * 13, window_layout=(0, 1, 1, 1) * 13),
    # the same block at test size (tests/test_layer_kinds.py)
    "tiny-smallthinker": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=6, n_kv_heads=2,
        head_dim=16, d_ff=32, max_seq_len=128, dtype=jnp.float32,
        remat=False, moe_experts=8, moe_top_k=3, moe_d_ff=32,
        moe_act="relu", moe_dropless=True, moe_router_pre_attn=True,
        sliding_window=8, rope_layout=(0, 1, 1, 1) * 2,
        window_layout=(0, 1, 1, 1) * 2),
    # Olmo-Hybrid-7B (allenai) as published: a 4-layer period of three
    # Gated DeltaNet layers (30 heads, keys of 96, values of 192, a
    # convolution of 4 taps, write strength in (0, 2)) and one
    # full-attention layer (30 heads of 128, QK-norm, post-norm, no
    # rotation anywhere)
    "olmo-hybrid-7b": TransformerConfig(
        vocab_size=100352, d_model=3840, n_layers=32, n_heads=30,
        n_kv_heads=30, head_dim=128, d_ff=11008, max_seq_len=65536,
        rope_theta=None, norm_eps=1e-6, qk_norm=True, post_norm=True,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 8,
        linear_key_heads=30, linear_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel=4, linear_allow_neg_eigval=True),
    # the same blocks at test size: two periods
    # (tests/test_olmo_hybrid.py)
    "tiny-olmo-hybrid": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=128, max_seq_len=256, dtype=jnp.float32,
        remat=False, rope_theta=None, qk_norm=True, post_norm=True,
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2,
        linear_key_heads=4, linear_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=32, linear_conv_kernel=4,
        linear_allow_neg_eigval=True),
    # Kanana-2-30B-A3B-Instruct (kakaocorp, model_type deepseek_v3) as
    # published: latent attention (32 heads, latent 512 + 64 rotated,
    # queries 128 + 64, values 128, no q_lora), layer 0 a dense SwiGLU
    # of 6144, then 47 layers of 128 sigmoid-routed experts of 768
    # (top-6 by score + bias, renormalised, x 2.448) and two shared
    "kanana-2-30b-a3b": TransformerConfig(
        vocab_size=128256, d_model=2048, n_layers=48, n_heads=32,
        n_kv_heads=32, head_dim=192, d_ff=6144, max_seq_len=32768,
        rope_theta=1e6, norm_eps=1e-6, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_interleave=True, moe_experts=128, moe_top_k=6, moe_d_ff=768,
        moe_dropless=True, moe_scoring="sigmoid", moe_route_scale=2.448,
        moe_shared_experts=2, first_dense_layers=1),
    # the same blocks at test size (tests/test_latent_attention.py): the
    # latent row is 32 + 8 wide, one dense layer then three of 8 experts
    "tiny-kanana": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=4,
        head_dim=24, d_ff=128, max_seq_len=256, dtype=jnp.float32,
        remat=False, rope_theta=1e4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
        moe_experts=8, moe_top_k=3, moe_d_ff=32, moe_dropless=True,
        moe_scoring="sigmoid", moe_route_scale=2.448,
        moe_shared_experts=2, first_dense_layers=1),
    # NVIDIA-Nemotron-3-Super-120B-A12B (model_type nemotron_h) as
    # published: 88 layers of ONE mixer each by hybrid_override_pattern
    # (M Mamba-2: 128 heads of 64, state 128, 8 groups, conv 4; E
    # LatentMoE: 512 sigmoid-routed squared-ReLU experts of 2688 in a
    # latent of 1024, top-22, x 5, one shared expert of 5376; *
    # attention with 32 query and 2 KV heads of 128, no rotation)
    "nemotron-3-super-120b-a12b": TransformerConfig(
        vocab_size=131072, d_model=4096, n_layers=88, n_heads=32,
        n_kv_heads=2, head_dim=128, d_ff=2688, max_seq_len=262144,
        rope_theta=None, norm_eps=1e-5,
        layer_types=pattern_layer_types(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        mamba_heads=128, mamba_head_dim=64, ssm_state_size=128,
        mamba_groups=8, mamba_conv_kernel=4, mamba_chunk=128,
        moe_experts=512, moe_top_k=22, moe_d_ff=2688, moe_act="relu2",
        moe_dropless=True, moe_scoring="sigmoid",
        moe_route_scale=5.0, moe_latent_size=1024, moe_shared_d_ff=5376),
    # the same layers at test size: the first 11-layer segment's
    # pattern (5 : 5 : 1), tests/test_nemotron_h.py
    "tiny-nemotron-h": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=11, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=32, max_seq_len=256, dtype=jnp.float32,
        remat=False, rope_theta=None, norm_eps=1e-5,
        layer_types=pattern_layer_types("MEMEMEMEM*E"),
        mamba_heads=8, mamba_head_dim=16, ssm_state_size=16,
        mamba_groups=2, mamba_conv_kernel=4, mamba_chunk=8,
        moe_experts=16, moe_top_k=5, moe_d_ff=32, moe_act="relu2",
        moe_dropless=True, moe_scoring="sigmoid",
        moe_route_scale=5.0, moe_latent_size=32, moe_shared_d_ff=48),
    # granite-4.0-h-micro (ibm-granite, model_type granitemoehybrid) as
    # published, whole: a 10-layer period of nine mamba2_mlp layers (64
    # heads of 64, state 128, ONE group, conv 4, prompts in chunks of
    # 256) and one full_attention layer at place 5 (32 query heads on 8
    # KV heads of 64, no rotation), a dense SwiGLU of 8192 in every
    # layer (num_local_experts 0: the family's shared_mlp alone), a tied
    # head, and the four muP multipliers
    "granite-4.0-h-micro": TransformerConfig(
        vocab_size=100352, d_model=2048, n_layers=40, n_heads=32,
        n_kv_heads=8, head_dim=64, d_ff=8192, max_seq_len=131072,
        rope_theta=None, norm_eps=1e-5, tie_embeddings=True,
        layer_types=(("mamba2_mlp",) * 5 + ("full_attention",)
                     + ("mamba2_mlp",) * 4) * 4,
        mamba_heads=64, mamba_head_dim=64, ssm_state_size=128,
        mamba_groups=1, mamba_conv_kernel=4, mamba_chunk=256,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0),
    # the same blocks at test size (tests/test_granite_h.py): two
    # periods of four layers, the attention layer at place 2, every
    # multiplier another number than 1 (and the softmax scale not 16^-1/2)
    "tiny-granite-h": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, max_seq_len=256, dtype=jnp.float32,
        remat=False, rope_theta=None, norm_eps=1e-5, tie_embeddings=True,
        layer_types=("mamba2_mlp", "mamba2_mlp", "full_attention",
                     "mamba2_mlp") * 2,
        mamba_heads=8, mamba_head_dim=16, ssm_state_size=16,
        mamba_groups=1, mamba_conv_kernel=4, mamba_chunk=8,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.125, logits_scaling=8.0),
    # K-EXAONE-236B-A23B (LGAI-EXAONE, model_type exaone_moe) as
    # published: 64 query / 8 KV heads of 128 with a per-head QK norm, a
    # 4-layer period of three rotating layers with a window of 128 and
    # one global layer without positions, layer 0 a dense SwiGLU of
    # 18432, then 47 layers of 128 sigmoid-routed experts of 2048 (top-8
    # by score + bias, renormalised, x 2.5) and one shared, and one
    # multi-token-prediction module
    "k-exaone-236b-a23b": TransformerConfig(
        vocab_size=153600, d_model=6144, n_layers=48, n_heads=64,
        n_kv_heads=8, head_dim=128, d_ff=18432, max_seq_len=262144,
        rope_theta=1e6, norm_eps=1e-5, qk_norm=True, qk_norm_per_head=True,
        sliding_window=128, rope_layout=(1, 1, 1, 0) * 12,
        window_layout=(1, 1, 1, 0) * 12, moe_experts=128, moe_top_k=8,
        moe_d_ff=2048, moe_dropless=True, moe_scoring="sigmoid",
        moe_route_scale=2.5, moe_shared_experts=1, first_dense_layers=1,
        mtp_layers=1),
    # Kimi-Linear-48B-A3B-Instruct (moonshotai, model_type kimi_linear) as
    # published: 27 layers, 20 of Kimi Delta Attention (32 heads, keys
    # and values of 128, convolutions of 4 taps, gates through a rank of
    # 128) and 7 of latent attention that does not rotate
    # (mla_use_nope; latent 512 + 64, queries 128 + 64, values 128) at
    # places 3, 7, 11, 15, 19, 23 and 26: six periods of 3 + 1 and a
    # partial one of 2 + 1; layer 0 a dense SwiGLU of 9216, then 26
    # layers of 256 sigmoid-routed experts of 1024 (top-8 by score +
    # bias, renormalised, x 2.446, no group limit) and one shared
    "kimi-linear-48b-a3b": TransformerConfig(
        vocab_size=163840, d_model=2304, n_layers=27, n_heads=32,
        n_kv_heads=32, head_dim=192, d_ff=9216, max_seq_len=1048576,
        rope_theta=None, norm_eps=1e-5, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        layer_types=(("kda",) * 3 + ("full_attention",)) * 6
        + ("kda", "kda", "full_attention"), layer_period=4,
        linear_key_heads=32, linear_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel=4, linear_gate_rank=128, moe_experts=256,
        moe_top_k=8, moe_d_ff=1024, moe_dropless=True,
        moe_scoring="sigmoid", moe_route_scale=2.446,
        moe_shared_experts=1, first_dense_layers=1),
    # the same blocks at test size (tests/test_kimi_linear.py), the same
    # shape of pattern: a dense first layer in a head run of one period,
    # two scanned periods, a partial one; keys of 8 and values of 32
    "tiny-kimi-linear": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=15, n_heads=4, n_kv_heads=4,
        head_dim=24, d_ff=128, max_seq_len=256, dtype=jnp.float32,
        remat=False, rope_theta=None, norm_eps=1e-5, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        layer_types=(("kda",) * 3 + ("full_attention",)) * 3
        + ("kda", "kda", "full_attention"), layer_period=4,
        linear_key_heads=4, linear_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=32, linear_conv_kernel=4, linear_gate_rank=8,
        moe_experts=16, moe_top_k=3, moe_d_ff=32, moe_dropless=True,
        moe_scoring="sigmoid", moe_route_scale=2.446,
        moe_shared_experts=1, first_dense_layers=1),
    # the same blocks at test size (tests/test_k_exaone.py): a window of
    # 8, one dense layer then five of 8 experts, the module
    "tiny-k-exaone": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=6, n_heads=8, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=256, dtype=jnp.float32,
        remat=False, rope_theta=1e6, norm_eps=1e-5, qk_norm=True,
        qk_norm_per_head=True, sliding_window=8,
        rope_layout=(1, 1, 1, 0) * 2, window_layout=(1, 1, 1, 0) * 2,
        moe_experts=8, moe_top_k=3, moe_d_ff=32, moe_dropless=True,
        moe_scoring="sigmoid", moe_route_scale=2.5, moe_shared_experts=1,
        first_dense_layers=1, mtp_layers=1),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    base = PRESETS[name]
    if not overrides:
        return base
    for derived, of in (("head_dim", base.d_model // base.n_heads),
                        ("moe_d_ff", base.d_ff)):
        # a size the preset left to its default follows the overrides
        if derived not in overrides and getattr(base, derived) == of:
            overrides[derived] = None
    return dataclasses.replace(base, **overrides)
