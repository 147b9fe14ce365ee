"""Named model configs mirroring the reference's eval configs.

BASELINE.json names five configs (the reference checkout was never mounted —
SURVEY.md §0): tiny 2L/128d LM ("CPU eager ref"), LRA ListOps/Text with
linear and softmax attention, 1.3B linear-attn LM (C4), 7B hybrid
(sliding-window softmax + global linear), and the recurrent decode path.
Each is a ``ModelConfig`` here; `get_config(name)` resolves them for the
CLI. Configs are plain frozen dataclasses overridable via
``dataclasses.replace`` or JSON/CLI flags (utils/config.py).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


def hybrid_pattern(n_layers: int, period: int = 4) -> Tuple[str, ...]:
    """swa,swa,...,linear repeating: every ``period``-th layer is global
    linear attention, the rest sliding-window softmax (the 7B hybrid
    layout: local mixing cheap, global mixing O(T))."""
    return tuple(
        "linear" if (i + 1) % period == 0 else "swa" for i in range(n_layers)
    )


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    vocab_size: int = 32000
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    head_dim: Optional[int] = None  # default d_model // n_heads
    mlp_hidden: Optional[int] = None  # default 4*d_model (gelu) / 8/3 (swiglu)
    # "swiglu": three matrices, silu(gate) * up; "gelu" and "relu2" (the
    # squared ReLU, max(x, 0)^2): two matrices, no gate. Dense layers, routed
    # experts and the shared expert all take the one form
    mlp: str = "swiglu"  # "swiglu" | "gelu" | "relu2"
    # a comma list of the blocks (0-based) that are a mixer ALONE: x +
    # mixer(norm1(x)) and nothing after it, no ``norm2`` and no feed-forward
    # part (a published layer that is one residual step, where its neighbour
    # has no feed-forward step to pair with). "": every block has both
    mixer_only: str = ""
    # "rmsnorm_zero": zero-centred RMSNorm, x * rsqrt(mean x^2 + 1e-6) *
    # (1 + w) with w initialised 0
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm" | "rmsnorm_zero"
    # per layer one of LAYER_TYPES; default all "linear"
    layer_types: Optional[Tuple[str, ...]] = None
    window: int = 512  # swa window
    # flash-attention tile sizes for the SINGLE-SHARD causal softmax/swa
    # flash paths (train __call__ and prefill; the sp ring/halo bodies
    # carry their own block constants in parallel/ring.py). With the
    # banded swa grid (ops/pallas/flash_attention.py, r5) smaller
    # attn_block_k trims boundary-tile mask padding without growing the
    # sweep; chip-swept in round 5 (BASELINE.md)
    attn_block_q: int = 512
    attn_block_k: int = 512
    feature_map: str = "elu1"  # linear-attn phi
    max_seq_len: int = 2048
    tie_embeddings: bool = True
    # "learned": absolute position embeddings added at the input; "none":
    # no position term there (the gated layers carry position themselves:
    # rotary in gated_softmax, decay and the short conv in gated_delta)
    pos_embed: str = "learned"
    # -- "gated_softmax" layers (models/mixers/gated_softmax.py): grouped KV
    # heads, rotary on the first rotary_dims of each head (halves rotated,
    # base rotary_base), per-head RMSNorm of q and k, a sigmoid output gate
    n_kv_heads: Optional[int] = None  # default n_heads
    rotary_dims: Optional[int] = None  # default the whole head
    rotary_base: float = 10000.0
    # -- "gated_delta" layers (models/mixers/gated_delta.py): key heads are
    # repeated to the value heads; q, k and v pass a causal depthwise conv +
    # SiLU
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0  # per head
    gdn_value_dim: int = 0  # per head
    gdn_conv_width: int = 4
    # write strength beta = 2 sigmoid(b) in (0, 2) instead of (0, 1): the
    # state's transition I - beta k k^T may then have a negative eigenvalue
    gdn_allow_neg_eigval: bool = False
    # "projection": RMSNorm (own weight) of the whole q and k projections,
    # before the split into heads (linear / softmax / swa layers); "head":
    # RMSNorm over each head's own width, one learned [head_dim] weight
    # (decay_linear / block_sparse layers)
    qk_norm: str = "none"  # "none" | "projection" | "head"
    rotary: bool = True  # softmax / swa layers rotate q and k by position
    # the layer kinds of those that do, a comma list, where only some rotate
    # (window layers that carry a position term beside full layers that carry
    # none); None: every softmax / swa layer, as ``rotary`` says
    rotary_layers: Optional[str] = None
    # softmax / swa layers multiply the attention's merged output by
    # sigmoid(W_g u), a projection of its own as wide as q's, before W_o
    attn_gate: bool = False
    # "pre": x + f(norm(x)); "post": x + norm(f(x)), the sublayer's OUTPUT
    # normalised before the residual add; "sandwich": x + norm_post(f(
    # norm(x))), both, four norms a block (``post_norm1`` / ``post_norm2``)
    norm_placement: str = "pre"  # "pre" | "post" | "sandwich"
    # -- "latent" layers (models/mixers/latent.py): queries through a normed
    # bottleneck of latent_q_rank, keys and values through a normed latent
    # of latent_kv_rank plus ONE rotary key of latent_rope_dim shared by
    # all heads (interleaved pairs, base rotary_base); a head's query and
    # key are [latent_nope_dim | latent_rope_dim] wide, its value
    # latent_value_dim; the decode state is the latent and the rotary key
    latent_q_rank: int = 0
    latent_kv_rank: int = 0
    latent_nope_dim: int = 0
    latent_rope_dim: int = 0
    latent_value_dim: int = 0
    # -- "decay_linear" layers (models/mixers/decay_linear.py): linear
    # attention with no feature map and no normaliser, S_t = lam_h S_{t-1} +
    # k_t^T v_t, the per-head decay fixed: lam_h = exp(-2^(-decay_exponent
    # h / n_heads)), h = 1..n_heads
    decay_exponent: float = 8.0
    # -- "block_sparse" layers (models/mixers/block_sparse.py): softmax
    # attention over n_kv_heads grouped KV heads; past sparse_dense_len
    # positions a query attends to sparse_topk key blocks of sparse_block
    # tokens, chosen per KV head from keys mean-pooled over sparse_kernel
    # tokens every sparse_stride; the first sparse_init_blocks blocks and
    # those of the last sparse_window tokens are always among them
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_topk: int = 64
    sparse_dense_len: int = 8192
    # -- "indexed" layers (models/mixers/indexed.py): grouped softmax
    # attention (rotary in the rotate-half pairing, base rotary_base) whose
    # every token attends to the index_topk earlier tokens that a learned
    # indexer scores highest: index_heads query heads of index_dim against
    # ONE index key of index_dim a token, held in the cache beside K and V.
    # index_topk 0: no such layer (every other layer's programs as they were)
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # the embedding's output, each residual branch (h = x + residual_scale *
    # f(norm(x))) and the final-normed hidden state before the head are
    # multiplied by these; 1.0 leaves the program as it was
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # std of the token embedding's normal initialiser; None: flax's own
    # (d_model^-1/2). A TIED head under random weights scores the input
    # token e . e ~ |e|^2 above every other: at flax's std a served model
    # repeats its prompt's last token whatever its layers compute
    embed_init_std: Optional[float] = None
    # "float32": projections, the embedding, the head and the state-space
    # conv DRAW their initial values in float32 and round them to
    # param_dtype; None: drawn in param_dtype itself. jax draws bfloat16
    # normals from 7 random bits: their mean is -0.018 sigma, so a
    # 2,048-wide projection maps the all-ones direction to an offset of
    # -0.8 of its output's size, and a deep stack of such weights answers
    # every prompt with one token (PERF.md section 6, PR 41)
    param_init_dtype: Optional[str] = None
    # -- "ssm" layers (models/mixers/ssm.py): a state-space layer with a
    # scalar decay per head that depends on the token, S_t = exp(dt_t A_h)
    # S_{t-1} + dt_t x_t B_t^T, ssm_heads heads of ssm_head_dim x ssm_state;
    # B_t and C_t are shared by the ssm_heads / ssm_groups heads of a group,
    # and the gated norm before the output projection is taken over each
    # group's channels (one norm over all of them at ssm_groups 1): the one
    # field fixes both groupings, so several B / C groups under a SINGLE norm
    # over all channels cannot be said (no model served here is of that form);
    # x, B and C pass a causal depthwise conv (ssm_conv_width taps, a bias)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_width: int = 4
    # softmax layers scale q . k by this instead of head_dim^-1/2
    attn_scale: Optional[float] = None
    norm_eps: float = 1e-6  # of every "rmsnorm" the model builds
    dropout: float = 0.0
    # numerics / execution
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"
    backend: str = "auto"  # kernel dispatch for attention ops
    chunk: Optional[int] = None  # linear-attn chunk size (None = tuned default)
    remat: bool = False  # per-block activation checkpointing
    # leave the last remat_skip blocks UN-rematted (identical math, they
    # keep their activations instead of recomputing the forward in the
    # backward pass). Each skipped flagship block trades ~1.6GB of saved
    # activations for ~22ms of recompute (BASELINE.md train-step profile);
    # the fused-CE loss (ops/fused_ce.py) frees enough temp HBM to pay for
    # several. Ignored when remat=False.
    remat_skip: int = 0
    # sequence/context parallelism: when True and the model is built with a
    # mesh whose sp axis > 1, causal attention runs sharded over tokens —
    # linear layers via the kv-state exclusive prefix (parallel/sequence.py),
    # softmax/swa layers via ring attention (parallel/ring.py)
    sequence_parallel: bool = False
    # load-balanced striped ring (parallel/ring.py docstring) for FULL-causal
    # softmax layers under sp: equal work on every ring step, removing the
    # plain causal ring's ~2x critical-path imbalance, at the cost of one
    # all_to_all per tensor. swa layers always keep the contiguous ring.
    # Needs seq_len % sp^2 == 0.
    ring_striped: bool = False
    # mixture-of-experts (models/moe.py): n_experts > 0 replaces the MLP of
    # every moe_period-th block with a routed expert MLP; expert weights
    # shard over the mesh's ep axis (parallel/sharding.py)
    n_experts: int = 0
    moe_period: int = 2  # every moe_period-th block is MoE
    moe_top_k: int = 1  # 1 = Switch routing
    moe_capacity_factor: float = 1.25
    # dropless routing (models/moe.py): tokens sorted by expert and run
    # through jax.lax.ragged_dot — every token reaches every chosen expert
    # (no capacity, no train/serve asymmetry). Single-host meshes only
    # (dp/fsdp/tp); capacity dispatch remains the ep-scalable path.
    moe_dropless: bool = False
    # dropless on ep meshes (models/moe.py::_dropless_ep): static per-shard
    # row budget = moe_ep_buffer * (routed rows) / ep. XLA's static shapes
    # make {truly dropless, ep-sharded, compute proportional to routed
    # rows} a pick-two: >= ep is mathematically dropless (every shard can
    # absorb every row) at replicated-compute cost; smaller values keep
    # compute ~balanced and drop only under extreme router imbalance —
    # counted in the "moe_stats" collection, never silent.
    moe_ep_buffer: float = 2.0
    moe_group_size: int = 512  # GShard local-group length (0 = whole row)
    moe_aux_weight: float = 1e-2  # load-balance loss weight
    moe_zloss_weight: float = 1e-3  # router z-loss weight
    # one chip's share of an expert-parallel layer (dropless path only):
    # the router keeps its published width and top-k over all of it, while
    # n_experts counts the experts HELD here, ids [moe_expert_offset,
    # moe_expert_offset + n_experts). The layer computes its own experts'
    # part of the result; what the absent ones would add is left out (their
    # chips compute it). 0 = the router is n_experts wide, all are held.
    # The held rows ride a static budget of moe_ep_buffer x their even share.
    moe_router_width: int = 0
    moe_expert_offset: int = 0
    # > 0 adds a shared expert of this width to every MoE layer, scaled by
    # sigmoid(w . x) unless moe_shared_gated is off (then added as it is)
    moe_shared_hidden: int = 0
    moe_shared_gated: bool = True
    # the router's scores (dropless paths): "softmax" over its whole width,
    # or "sigmoid" of each logit; the top-k are chosen on the scores and
    # renormalised over the k chosen, then multiplied by moe_route_scale
    moe_score: str = "softmax"  # "softmax" | "sigmoid"
    moe_route_scale: float = 1.0
    # > 0: a per-expert fp32 bias [router width] is added to the scores for
    # the SELECTION of the top-k only; the weights come from the scores
    # without it. A buffer no gradient trains (a balancing rule would move
    # it; none is built): under random weights it is drawn normal at this
    # standard deviation. 0: no such leaf, the router as it was
    moe_route_bias: float = 0.0
    # > 0: the chosen experts' weights are s_e / (sum over the chosen + this)
    # as a family publishes it; 0: s_e / max(sum, 1e-9), the router as it was
    moe_gate_eps: float = 0.0
    # a routed expert's width where it differs from the dense layers'
    # mlp_hidden (0: resolved_mlp_hidden serves both)
    moe_hidden: int = 0
    # the first moe_first_dense blocks keep a dense MLP whatever moe_period
    moe_first_dense: int = 0
    # > 0: the routed experts work in a latent of this width: u W_dn (d_model
    # -> moe_latent) goes in, the experts are [moe_latent, moe_hidden] and
    # back, and their weighted sum passes W_up (moe_latent -> d_model); the
    # router and the shared expert read the block's own input. No bias, norm
    # or activation on either projection. Dropless layers of one device
    moe_latent: int = 0
    # the held-rows serving path's row tile where a call brings an expert at
    # most this many rows on average (a decode step: slots x k pairs over the
    # whole router): the buffer is padded by a tile an expert, so at 128 a
    # step of 512 pairs over 128 experts sorts, gathers and multiplies 16,896
    # rows to serve 512. 128: every call takes the 128-row tile, as it did
    moe_step_tile: int = 128
    # serving, where the carry is held once: this many slots' prompt pieces
    # run as ONE program whose feed-forward layers see all their rows at once
    # (each piece still attends alone). A piece of 1,024 rows brings each of
    # 128 experts 64 rows, so a piece alone streams every expert's weights
    # for half a tile of work. 1: a program a piece, as it was
    prefill_group: int = 1
    # classifier-only
    n_classes: int = 0  # >0 => LRA classifier head

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def resolved_mlp_hidden(self) -> int:
        if self.mlp_hidden:
            return self.mlp_hidden
        if self.mlp == "swiglu":
            # 8/3 * d rounded up to a multiple of 128 (TPU lane width)
            h = int(self.d_model * 8 / 3)
            return max(128, (h + 127) // 128 * 128)
        return 4 * self.d_model

    def has_mlp(self, layer: int) -> bool:
        """Does block ``layer`` (0-based) have a feed-forward part?"""
        return str(layer) not in self.mixer_only.split(",")

    def moe_at(self, layer: int) -> bool:
        """Does block ``layer`` (0-based) carry a routed-expert MLP?"""
        return (
            self.n_experts > 0
            and layer >= self.moe_first_dense
            and (layer + 1) % self.moe_period == 0
            and self.has_mlp(layer)
        )

    def block_form(self, layer: int) -> Dict[str, bool]:
        """What every builder of block ``layer`` hands ``Block`` beside its
        mixer's type (``Block(cfg, kind, ..., **cfg.block_form(i))``): the
        one place that says which feed-forward part a block has, so no
        builder can leave a part of the rule out."""
        return {"use_moe": self.moe_at(layer), "mixer_only": not self.has_mlp(layer)}

    @property
    def moe_held(self) -> bool:
        """Are the MoE layers one chip's share of an expert-parallel layer
        (models/moe.py::_dropless_held)? A router as wide as the experts
        held, however that width is spelled, is no share."""
        return self.n_experts > 0 and bool(
            self.resolved_router_width != self.n_experts or self.moe_expert_offset
        )

    @property
    def resolved_moe_hidden(self) -> int:
        return self.moe_hidden or self.resolved_mlp_hidden

    @property
    def resolved_router_width(self) -> int:
        return self.moe_router_width or self.n_experts

    @property
    def resolved_layer_types(self) -> Tuple[str, ...]:
        lt = self.layer_types or ("linear",) * self.n_layers
        assert len(lt) == self.n_layers, (lt, self.n_layers)
        for t in lt:
            assert t in LAYER_TYPES, t
        return lt


# one mixer class each: models/mixers/__init__.py::MIXERS
LAYER_TYPES = (
    "linear", "softmax", "swa", "gated_delta", "gated_softmax",
    "decay_linear", "block_sparse", "ssm", "latent", "indexed", "gated_conv",
)


def gated_pattern(n_layers: int, period: int = 4) -> Tuple[str, ...]:
    """gated_delta x (period - 1) then gated_softmax, repeating."""
    return tuple(
        "gated_softmax" if (i + 1) % period == 0 else "gated_delta"
        for i in range(n_layers)
    )


# Source scopes whose fp32 matmuls are SANCTIONED under the bf16 compute
# policy — the declared exceptions the jaxpr contract auditor
# (orion_tpu/analysis/jaxpr_audit.py::audit_matmul_bf16) checks the traced
# train step against. Entries are 'file.py' or 'file.py::function', matched
# against each dot_general's source frames ('dir/file.py' where another
# directory holds a file of the same name). Everything here is the fp32
# (S, z) kv-state accumulation contract: linear attention keeps its running
# state in fp32 regardless of the activation dtype (the chunked scan, the
# pallas state carries, the sp exclusive-prefix exchange, and the FAVOR+
# feature map's numerically-sensitive projection).
F32_MATMUL_SCOPES = (
    "linear_attention.py",          # chunked-scan fp32 state accumulation
    "causal_dot.py",                # pallas state init/carry helpers
    "sequence.py",                  # sp exclusive-prefix fp32 state math
    "linear.py::_phi_map",          # FAVOR+ fp32 random-feature projection
    # delta-rule fp32 state + triangular inverse, and its kernels' wrapper
    # (not the mixer of the same file name, models/mixers/gated_delta.py)
    "ops/gated_delta.py",
    "pallas/gated_delta.py",
    "ops/ssm.py",                   # the state-space layers' fp32 state
)


TINY = ModelConfig(
    name="tiny",
    vocab_size=256,  # byte-level
    d_model=128,
    n_layers=2,
    n_heads=4,
    max_seq_len=512,
    dtype="float32",
    remat=False,
)

LM_1B3 = ModelConfig(
    name="lm_1b3",
    vocab_size=32000,
    d_model=2048,
    n_layers=24,
    n_heads=16,
    max_seq_len=2048,
    dtype="bfloat16",
    remat=True,
    # un-rematted blocks: a trade of HBM for recompute, chosen by earlier
    # rounds' sweeps on another compiler. Under the installed one, for one
    # 16GB v5e at T 2048 with adafactor and the Pallas kernels
    # (tests/test_chip_compile.py, PERF.md "Cells"): b12 x skip6 x
    # bfloat16_sr compiles AND runs on the chip (chip_smoke.py's train
    # phase); b16 x skip4 and b16 x skip6 (bfloat16_sr), b16 x skip4 and
    # b12 x skip6 (float32) compile for the chip, not run. Which is
    # fastest: not measured on the current installation.
    remat_skip=4,
)

HYBRID_7B = ModelConfig(
    name="hybrid_7b",
    vocab_size=32000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    layer_types=hybrid_pattern(32, period=4),
    window=1024,
    max_seq_len=4096,
    dtype="bfloat16",
    remat=True,
)

HYBRID_1B3 = ModelConfig(
    # chip-sized hybrid (M4 evidence, VERDICT r2 #4): the 7B layout — swa
    # W=1024 with a global linear layer every 4th block — at lm_1b3 width,
    # so rotary + flash-swa + linear kernels + remat interact in ONE real
    # measured train step on the 16GB chip (hybrid_7b only AOT-compiles).
    name="hybrid_1b3",
    vocab_size=32000,
    d_model=2048,
    n_layers=24,
    n_heads=16,
    layer_types=hybrid_pattern(24, period=4),
    window=1024,
    max_seq_len=2048,
    dtype="bfloat16",
    remat=True,
    # chosen by the same earlier sweep as LM_1B3's; which points fit under
    # the installed compiler: see PERF.md "Cells" (not run on the chip)
    remat_skip=4,
)

MOE_1B3_8E = ModelConfig(
    # sparse sibling of LM_1B3: same base width, every other MLP routed over
    # 8 experts (4.125B params total, 1.284B active per token with top-1).
    # Pod-scale: does NOT fit one 16GB chip — shard experts over ep
    # (16.5GB fp32 weights alone); single-chip validation is the AOT
    # planning path (orion_tpu/aot.py), like hybrid_7b.
    name="moe_1b3_8e",
    vocab_size=32000,
    d_model=2048,
    n_layers=24,
    n_heads=16,
    max_seq_len=2048,
    dtype="bfloat16",
    remat=True,
    n_experts=8,
    moe_period=2,
    moe_top_k=1,
)

MOE_1B3_4E = dataclasses.replace(
    # chip-scale sparse config (1.893B total, same 1.284B active/token):
    # every 4th MLP routed over 4 experts — sized for the single 16GB chip
    # (rounds 1-5 measured it, BASELINE.md; no cell of the benchmark does)
    MOE_1B3_8E, name="moe_1b3_4e", n_experts=4, moe_period=4,
)

QWEN3_NEXT_80B = ModelConfig(
    # Qwen3-Next-80B-A3B at its published widths, as ONE chip's share of an
    # 8-way expert- and vocabulary-parallel deployment, one period deep
    # (benchmark/configs/qwen3_next_80b.json states the source, the cut and
    # what is assumed): 3 gated delta-rule layers then 1 gated GQA softmax
    # layer, every MLP a 512-way top-10 MoE with a gated shared expert; 64
    # of the 512 experts and 18,992 of the 151,936 vocabulary rows are held.
    name="qwen3_next_80b",
    vocab_size=18992,
    d_model=2048,
    n_layers=4,
    layer_types=gated_pattern(4, period=4),
    n_heads=16,
    n_kv_heads=2,
    head_dim=256,
    rotary_dims=64,
    rotary_base=1e7,
    gdn_key_heads=16,
    gdn_value_heads=32,
    gdn_key_dim=128,
    gdn_value_dim=128,
    gdn_conv_width=4,
    norm="rmsnorm_zero",
    pos_embed="none",
    tie_embeddings=False,
    mlp="swiglu",
    mlp_hidden=512,  # one routed expert's width
    n_experts=64,
    moe_router_width=512,
    moe_expert_offset=0,
    moe_top_k=10,
    moe_period=1,
    moe_dropless=True,
    moe_ep_buffer=1.5,  # the held rows' buffer: 1.5 x their even share
    moe_shared_hidden=512,
    max_seq_len=8192,
    dtype="bfloat16",
    remat=True,
)

def delta_full_pattern(n_layers: int, period: int = 4) -> Tuple[str, ...]:
    """gated_delta x (period - 1) then full softmax attention, repeating."""
    return tuple(
        "softmax" if (i + 1) % period == 0 else "gated_delta"
        for i in range(n_layers)
    )


OLMO_HYBRID_7B = ModelConfig(
    # Olmo-Hybrid-7B at its published widths, two of its eight periods deep:
    # one of four pipeline stages of 8 layers (benchmark/configs/
    # olmo_hybrid_7b.json states the source, the cut and what is assumed).
    # 3 gated delta-rule layers (30 heads of 96 x 192, beta in (0, 2)) then
    # 1 full-attention layer (30 heads x 128, q/k norm over the projection,
    # no rotary), the sublayer's output normalised, dense SwiGLU; served in
    # bfloat16, parameters included.
    name="olmo_hybrid_7b",
    vocab_size=100352,
    d_model=3840,
    n_layers=8,
    layer_types=delta_full_pattern(8, period=4),
    n_heads=30,
    head_dim=128,
    gdn_key_heads=30,
    gdn_value_heads=30,
    gdn_key_dim=96,
    gdn_value_dim=192,
    gdn_conv_width=4,
    gdn_allow_neg_eigval=True,
    qk_norm="projection",
    rotary=False,
    norm_placement="post",
    norm="rmsnorm",
    pos_embed="none",
    tie_embeddings=False,
    mlp="swiglu",
    mlp_hidden=11008,
    max_seq_len=4096,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

def decay_sparse_pattern(n_layers: int, period: int = 4) -> Tuple[str, ...]:
    """decay_linear x (period - 1) then block_sparse, repeating."""
    return tuple(
        "block_sparse" if (i + 1) % period == 0 else "decay_linear"
        for i in range(n_layers)
    )


MINICPM_SALA = ModelConfig(
    # MiniCPM-SALA at its published widths, one period deep: the published
    # layers 13-16 (0-based), one of eight pipeline stages of 4 layers
    # (benchmark/configs/minicpm_sala.json states the source, the cut and
    # what is assumed). 3 decayed linear-attention layers (32 heads x 128,
    # rotary, no normaliser, output norm and gate) then 1 block-sparse
    # layer (32 query heads over 2 KV heads x 128, no rotary, top-64 blocks
    # of 64 past 8,192 tokens, output gate); per-head q / k norm; the
    # embedding x 12, residual branches x 1.4 / sqrt(32) (the PUBLISHED
    # depth), logits from h / 16; dense SwiGLU; served in bfloat16.
    name="minicpm_sala",
    vocab_size=73448,
    d_model=4096,
    n_layers=4,
    layer_types=decay_sparse_pattern(4, period=4),
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    qk_norm="head",
    rotary_base=10000.0,
    embed_scale=12.0,
    residual_scale=1.4 / 32 ** 0.5,
    logit_scale=256 / 4096,
    norm="rmsnorm",
    pos_embed="none",
    tie_embeddings=False,
    mlp="swiglu",
    mlp_hidden=16384,
    max_seq_len=16896,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

def ssm_full_pattern(n_layers: int, period: int = 10, at: int = 5) -> Tuple[str, ...]:
    """ssm everywhere but layers ``at``, ``at + period``, ..., which are
    full softmax attention."""
    return tuple(
        "softmax" if i % period == at else "ssm" for i in range(n_layers)
    )


GRANITE_4_0_H_MICRO = ModelConfig(
    # granite-4.0-h-micro at its published widths and depth (benchmark/
    # configs/granite_4_0_h_micro.json states the source and what is
    # assumed): 36 state-space layers (64 heads of 64 x 128, one group, a
    # biased conv of 4 taps, the gate before the norm) and 4 full-attention
    # layers at 5, 15, 25, 35 (32 query heads over 8 KV heads x 64, scale
    # 1 / 64, no rotary); no position term; the embedding x 12, residual
    # branches x 0.22, logits / 8; dense SwiGLU; tied head; bfloat16.
    name="granite_4_0_h_micro",
    vocab_size=100352,
    d_model=2048,
    n_layers=40,
    layer_types=ssm_full_pattern(40),
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    attn_scale=0.015625,
    rotary=False,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_state=128,
    ssm_groups=1,
    ssm_conv_width=4,
    embed_scale=12.0,
    residual_scale=0.22,
    logit_scale=1 / 8,
    embed_init_std=0.005,
    param_init_dtype="float32",
    norm="rmsnorm",
    norm_eps=1e-5,
    pos_embed="none",
    tie_embeddings=True,
    mlp="swiglu",
    mlp_hidden=8192,
    max_seq_len=2048,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

OPENPANGU_ULTRA_MOE_718B = ModelConfig(
    # openPangu-Ultra-MoE-718B at its published widths, as ONE chip's share
    # of a 16-way expert-parallel group, one leading dense layer and four
    # expert layers deep (benchmark/configs/openpangu_ultra_moe_718b.json
    # states the source, the cut and what is assumed): latent attention
    # (128 heads, queries through a 1,536-wide normed bottleneck, keys and
    # values through a 512-wide normed latent and one 64-wide rotary key
    # shared by all heads), sandwich norms, a sigmoid top-8 router over 256
    # experts of which 16 are held, an ungated shared expert; 19,200 of the
    # 153,600 vocabulary rows; served in bfloat16.
    name="openpangu_ultra_moe_718b",
    vocab_size=19200,
    d_model=7680,
    n_layers=5,
    layer_types=("latent",) * 5,
    n_heads=128,
    head_dim=192,
    latent_q_rank=1536,
    latent_kv_rank=512,
    latent_nope_dim=128,
    latent_rope_dim=64,
    latent_value_dim=128,
    rotary_base=25.6e6,
    norm="rmsnorm",
    norm_eps=1e-5,
    norm_placement="sandwich",
    pos_embed="none",
    tie_embeddings=False,
    mlp="swiglu",
    mlp_hidden=18432,
    moe_hidden=2048,
    moe_shared_hidden=2048,
    moe_shared_gated=False,
    moe_first_dense=1,
    moe_period=1,
    n_experts=16,
    moe_router_width=256,
    moe_expert_offset=0,
    moe_top_k=8,
    moe_score="sigmoid",
    moe_route_scale=2.5,
    moe_dropless=True,
    # the held rows' buffer holds every pair the router can send here
    # (router width / experts held x their even share): nothing can drop
    moe_ep_buffer=16.0,
    param_init_dtype="float32",
    max_seq_len=4608,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

KEYE_VL_2_0_30B_A3B = ModelConfig(
    # Keye-VL-2.0-30B-A3B's language model at its published widths, four of
    # its 48 layers deep: one pipeline stage that holds every expert of its
    # layers (benchmark/configs/keye_vl_2_0_30b_a3b.json states the source,
    # the cut and what is assumed). Every layer: grouped attention (32 query
    # heads over 4 KV heads x 128, per-head q / k norm, rotary base 1e7)
    # over the 2,048 cache rows a learned indexer picks for each token (16
    # index heads x 64 against one 64-wide index key a token), then a
    # softmax top-8 mixture of 128 SwiGLU experts of 768, renormalised over
    # the chosen, no shared expert; untied head over 151,936; bfloat16.
    name="keye_vl_2_0_30b_a3b",
    vocab_size=151936,
    d_model=2048,
    n_layers=4,
    layer_types=("indexed",) * 4,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    qk_norm="head",
    rotary_base=1e7,
    index_heads=16,
    index_dim=64,
    index_topk=2048,
    norm="rmsnorm",
    pos_embed="none",
    tie_embeddings=False,
    mlp="swiglu",
    moe_hidden=768,
    moe_period=1,
    n_experts=128,
    moe_top_k=8,
    moe_score="softmax",
    moe_dropless=True,
    moe_ep_buffer=1.0,  # the buffer holds every pair: nothing can drop
    param_init_dtype="float32",
    max_seq_len=33280,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

TRINITY_MINI = ModelConfig(
    # Trinity-Mini at its published widths, one leading dense layer and one
    # whole period of its expert layers deep: the published layers 0 and 4-7,
    # one of eight four-layer pipeline stages (benchmark/configs/
    # trinity_mini.json states the source, the cut and what is assumed).
    # Grouped attention (32 query heads over 4 KV heads x 128, per-head q / k
    # norm, a sigmoid output gate from its own projection) whose window
    # layers hold a 2,048-row ring and rotate q and k while every fourth
    # holds a growing cache and carries no position term; sandwich norms; the
    # embedding x sqrt(2,048); a dense SwiGLU of 6,144 then sigmoid top-8 of
    # 128 experts of 1,024 with a per-expert selection bias, renormalised
    # over the chosen x 2.826, beside an ungated shared expert; all held;
    # untied head over 200,192; served in bfloat16.
    name="trinity_mini",
    vocab_size=200192,
    d_model=2048,
    n_layers=5,
    layer_types=("swa", "swa", "swa", "swa", "softmax"),
    window=2048,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    qk_norm="head",
    rotary_layers="swa",
    attn_gate=True,
    embed_scale=2048 ** 0.5,
    norm="rmsnorm",
    norm_eps=1e-5,
    norm_placement="sandwich",
    pos_embed="none",
    tie_embeddings=False,
    mlp="swiglu",
    mlp_hidden=6144,
    moe_hidden=1024,
    moe_shared_hidden=1024,
    moe_shared_gated=False,
    moe_first_dense=1,
    moe_period=1,
    n_experts=128,
    moe_top_k=8,
    moe_score="sigmoid",
    moe_route_scale=2.826,
    moe_route_bias=0.02,
    moe_dropless=True,
    moe_ep_buffer=1.0,  # the buffer holds every pair: nothing can drop
    moe_step_tile=16,
    prefill_group=4,
    param_init_dtype="float32",
    max_seq_len=17408,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

def conv_full_pattern(periods: int, period: int = 4) -> Tuple[str, ...]:
    """One leading gated_conv layer, then ``periods`` times (softmax,
    gated_conv x (period - 1))."""
    return ("gated_conv",) + (
        ("softmax",) + ("gated_conv",) * (period - 1)
    ) * periods


LFM2_8B_A1B = ModelConfig(
    # LFM2-8B-A1B at its published widths, the first of two pipeline stages:
    # the published layer 0 (a leading dense gated_conv layer; the second
    # counts once) and layers 2-13, three whole periods of (full_attention,
    # conv, conv, conv) with ALL 32 experts of each (benchmark/configs/
    # lfm2_8b_a1b.json states the source, the cut and what is assumed).
    # The conv layers are a gated short convolution alone (three taps over
    # 2,048 channels, no bias, no activation; a slot's state is two rows);
    # the attention layers 32 query heads over 8 KV heads x 64, per-head q /
    # k norm, rotary at 1e6; a dense SwiGLU of 7,168 then sigmoid top-4 of
    # 32 experts of 1,792 with a per-expert selection bias, renormalised
    # over the chosen with the published 1e-6; tied head over 65,536; bf16.
    name="lfm2_8b_a1b",
    vocab_size=65536,
    d_model=2048,
    n_layers=13,
    layer_types=conv_full_pattern(3),
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    qk_norm="head",
    rotary_base=1e6,
    norm="rmsnorm",
    norm_eps=1e-5,
    pos_embed="none",
    tie_embeddings=True,
    embed_init_std=0.005,
    mlp="swiglu",
    mlp_hidden=7168,
    moe_hidden=1792,
    moe_first_dense=1,
    moe_period=1,
    n_experts=32,
    moe_top_k=4,
    moe_score="sigmoid",
    moe_route_scale=1.0,
    moe_route_bias=0.05,
    moe_gate_eps=1e-6,
    moe_dropless=True,
    moe_ep_buffer=1.0,  # the buffer holds every pair: nothing can drop
    moe_step_tile=32,
    # prefill_group stays 1: four slots' pieces in one program had XLA relay
    # every 64-wide cache whole around their write-back (46% of the cell's
    # busy time on the chip, PERF.md section 6, PR 55); a piece a program
    # updates a slot's rows where the cache lies
    param_init_dtype="float32",
    max_seq_len=2560,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

def step_pattern_blocks(pattern: str) -> Tuple[Tuple[str, ...], str]:
    """A published pattern of ONE residual step a layer (``M`` a state-space
    mixer, ``*`` full attention, ``E`` a feed-forward step) as this repo's
    blocks -> (``layer_types``, ``mixer_only``): a mixer and the ``E`` after
    it are one block, a mixer with no ``E`` after it a block that is a mixer
    alone. The mathematics is the same: ``x + F(N(x))`` twice is ``h = x +
    Mix(N1 x); y = h + F(N2 h)``. An ``E`` with no mixer before it has no
    block here and is refused."""
    kinds, alone = [], []
    steps = list(pattern)
    while steps:
        letter = steps.pop(0)
        assert letter in "M*", f"a feed-forward step with no mixer before it: {pattern!r}"
        kinds.append({"M": "ssm", "*": "softmax"}[letter])
        if steps and steps[0] == "E":
            steps.pop(0)
        else:
            alone.append(str(len(kinds) - 1))
    return tuple(kinds), ",".join(alone)


_NEMOTRON_STAGE_0 = step_pattern_blocks("MEMEMEM*EME")

NEMOTRON_3_SUPER_120B = ModelConfig(
    # NVIDIA-Nemotron-3-Super-120B-A12B at its published widths, as chip 0 of
    # the first of eight pipeline stages whose layers four chips share
    # (benchmark/configs/nemotron_3_super_120b.json states the source, the
    # cut and what is assumed): the published layers 0-10, MEMEMEM*EME, as six
    # blocks, (ssm, experts) x 3, ssm alone, (attention, experts), (ssm,
    # experts). The state-space layers are 128 heads of 64 x 128 in 8 groups
    # (a biased conv of 4 taps over 10,240 channels, the gate before a norm
    # over each group's 1,024 channels); the attention layer 32 query heads
    # over 2 KV heads x 128 with no position term; the experts work in a
    # 1,024-wide latent: a sigmoid top-22 router over 512 with a selection
    # bias, renormalised over the chosen x 5, 128 squared-ReLU experts of
    # 2,688 held (two matrices each), beside an ungated squared-ReLU shared
    # expert of 5,376 on the full width; untied head over 32,768 of the
    # 131,072 vocabulary rows; served in bfloat16.
    name="nemotron_3_super_120b",
    vocab_size=32768,
    d_model=4096,
    n_layers=6,
    layer_types=_NEMOTRON_STAGE_0[0],
    mixer_only=_NEMOTRON_STAGE_0[1],
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    rotary=False,
    ssm_heads=128,
    ssm_head_dim=64,
    ssm_state=128,
    ssm_groups=8,
    ssm_conv_width=4,
    norm="rmsnorm",
    norm_eps=1e-5,
    pos_embed="none",
    tie_embeddings=False,
    mlp="relu2",
    moe_hidden=2688,
    moe_latent=1024,
    moe_shared_hidden=5376,
    moe_shared_gated=False,
    moe_period=1,
    n_experts=128,
    moe_router_width=512,
    moe_expert_offset=0,
    moe_top_k=22,
    moe_score="sigmoid",
    moe_route_scale=5.0,
    moe_route_bias=0.02,
    moe_gate_eps=1e-20,
    moe_dropless=True,
    # the held rows' buffer holds every pair the router can send here
    # (router width / experts held x their even share): nothing can drop
    moe_ep_buffer=4.0,
    moe_step_tile=16,
    # prefill_group stays 1: four slots' pieces in one program had XLA relay
    # every layer's 0.54 GB state whole, slots minor, around their write-back
    # (6% of the cell's busy time, and 7% of its tokens/s against a piece a
    # program: PERF.md section 6, PR 57); a piece a program updates a slot's
    # row where the state lies
    param_init_dtype="float32",
    max_seq_len=4096,
    dtype="bfloat16",
    param_dtype="bfloat16",
)

LRA_LISTOPS_LINEAR = ModelConfig(
    name="lra_listops_linear",
    vocab_size=32,  # digits + operators + specials
    d_model=128,
    n_layers=4,
    n_heads=4,
    max_seq_len=2048,
    layer_types=("linear",) * 4,
    n_classes=10,
    dtype="float32",
    mlp="gelu",
    norm="layernorm",
)

LRA_LISTOPS_SOFTMAX = dataclasses.replace(
    LRA_LISTOPS_LINEAR, name="lra_listops_softmax", layer_types=("softmax",) * 4
)

LRA_TEXT_LINEAR = ModelConfig(
    name="lra_text_linear",
    vocab_size=256,  # byte level
    d_model=256,
    n_layers=4,
    n_heads=4,
    max_seq_len=4096,
    layer_types=("linear",) * 4,
    n_classes=2,
    dtype="float32",
    mlp="gelu",
    norm="layernorm",
)

LRA_TEXT_SOFTMAX = dataclasses.replace(
    LRA_TEXT_LINEAR, name="lra_text_softmax", layer_types=("softmax",) * 4
)

CONFIGS = {
    c.name: c
    for c in [
        TINY,
        LM_1B3,
        HYBRID_1B3,
        HYBRID_7B,
        MOE_1B3_8E,
        MOE_1B3_4E,
        QWEN3_NEXT_80B,
        OLMO_HYBRID_7B,
        MINICPM_SALA,
        GRANITE_4_0_H_MICRO,
        OPENPANGU_ULTRA_MOE_718B,
        KEYE_VL_2_0_30B_A3B,
        TRINITY_MINI,
        LFM2_8B_A1B,
        NEMOTRON_3_SUPER_120B,
        LRA_LISTOPS_LINEAR,
        LRA_LISTOPS_SOFTMAX,
        LRA_TEXT_LINEAR,
        LRA_TEXT_SOFTMAX,
    ]
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in CONFIGS:
        raise ValueError(f"unknown config {name!r}; have {sorted(CONFIGS)}")
    cfg = CONFIGS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


__all__ = [
    "ModelConfig", "CONFIGS", "get_config", "hybrid_pattern",
    "gated_pattern", "delta_full_pattern", "decay_sparse_pattern", "ssm_full_pattern", "conv_full_pattern",
    "step_pattern_blocks", "F32_MATMUL_SCOPES", "LAYER_TYPES",
]
