"""Token mixers: one file per mechanism, one registry, one contract.

``MIXERS[layer_type]`` is the class ``models/transformer.py::Block`` builds
as its ``attn`` submodule for every entry of ``configs.LAYER_TYPES``
(``linear``, ``softmax`` / ``swa``, ``gated_delta``, ``gated_softmax``,
``decay_linear``, ``block_sparse``, ``ssm``, ``latent``, ``indexed``,
``gated_conv``). A new
mechanism is one file here with a :class:`Mixer` subclass, its entry in
``LAYER_TYPES`` and in ``MIXERS`` below, and nothing else: ``Block``,
``TransformerLM``, ``init_decode_state``, the decode programs and the
serving engine ask the class, never the layer type's name. Arrows point one
way: ``configs`` <- ``mixers`` <- ``transformer`` <- ``generate`` / trainer.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import LAYER_TYPES, ModelConfig

Array = jax.Array
State = Dict[str, Array]


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def drawn_in(cfg: ModelConfig, init):
    """``init`` drawing in ``cfg.param_init_dtype`` where set, its values
    rounded to the dtype the parameter is held in; ``init`` itself
    otherwise."""
    if cfg.param_init_dtype is None:
        return init
    wide = _dtype(cfg.param_init_dtype)
    return lambda key, shape, dtype=wide: init(key, shape, wide).astype(dtype)


def drawn_kernel_init(cfg: ModelConfig) -> dict:
    """``nn.Dense``'s ``kernel_init`` keyword under ``cfg.param_init_dtype``
    (:func:`drawn_in` of flax's own initialiser); nothing where it is unset."""
    if cfg.param_init_dtype is None:
        return {}
    return {"kernel_init": drawn_in(cfg, nn.linear.default_kernel_init)}


def ungated_activation(mlp: str):
    """The activation of a two-matrix feed-forward form (``cfg.mlp`` other
    than "swiglu"): "gelu", or "relu2", the squared ReLU ``max(x, 0)^2``."""
    assert mlp in ("gelu", "relu2"), mlp
    return jax.nn.gelu if mlp == "gelu" else lambda v: jnp.square(jax.nn.relu(v))


def _dense_factory(cfg: ModelConfig, quant: str = "", mesh=None):
    """``(name, features) -> module``: the bias-free projection every layer
    uses, or its weight-streamed form in the decode modes. "int8": every
    matmul int8. "int4": matmul weights nibble-packed int4, while
    embedding/head (token-distribution-critical, table shared) and MoE
    expert stacks stay int8 — the mixed scheme VERDICT r3 #5 names.
    ``mesh`` reaches Int4Dense so its fused-kernel gate reflects the MODEL's
    mesh, not the host's device count (ADVICE r4: a single-device model on
    a multi-device host must not silently lose the kernel)."""
    dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
    if not quant:
        init = drawn_kernel_init(cfg)
        return lambda n, feats: nn.Dense(
            feats, use_bias=False, dtype=dt, param_dtype=pdt, name=n, **init
        )
    from orion_tpu.quant import Int4Dense, Int8Dense

    if quant == "int4":
        return lambda n, feats: Int4Dense(feats, dtype=dt, mesh=mesh, name=n)
    assert quant == "int8", quant
    return lambda n, feats: Int8Dense(feats, dtype=dt, name=n)


def kernel_bh(cfg: ModelConfig, mesh, fn, *args):
    """Kernel dispatch for per-(batch, head)-parallel attention: on a
    GSPMD mesh whose data axes split, a Mosaic kernel must be
    manualized (XLA cannot auto-partition tpu_custom_call) — shard_map
    over (dp, fsdp, tp) via parallel/kernel_shard.py; everywhere else
    the call goes straight through. A residual ``fn`` names for a rematted
    block's policy (the flash forward's output and rows) is kept in either
    form: ``shard_map``'s partial evaluation hands the policy down into its
    body, where the kept bytes are one device's shard
    (tests/test_remat_keeps.py)."""
    from orion_tpu.ops.dispatch import resolve
    from orion_tpu.parallel.kernel_shard import needs_manual, shard_map_bh

    b = resolve(cfg.backend)
    if needs_manual(mesh, b):
        # vma ON for real Mosaic (its lowering requires it in a
        # partial-manual region), OFF for interpret kernels (which
        # cannot trace under the check) — kernel_shard.py docstring
        return shard_map_bh(mesh, fn, *args, check_vma=(b != "pallas_interpret"))
    return fn(*args)


def whole_array_backend(cfg: ModelConfig, mesh) -> str:
    """The backend of a kernel that no shard_map is written for (the short
    conv: its batch would split over (dp, fsdp) and its channels over tp,
    not :func:`kernel_bh`'s heads): ``cfg.backend``, or the XLA form on a
    mesh whose data axes split, where a bare Mosaic call is refused."""
    from orion_tpu.ops.dispatch import resolve
    from orion_tpu.parallel.kernel_shard import needs_manual

    return "xla" if needs_manual(mesh, resolve(cfg.backend)) else cfg.backend


NORM_EPS = 1e-6


def _rms(x: Array) -> Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + NORM_EPS)


class ZeroCentredRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + 1e-6) * (1 + w)`` over the last axis, fp32
    inside, ``w`` initialised 0."""

    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        w = self.param(
            "scale", nn.initializers.zeros_init(), (x.shape[-1],), self.param_dtype
        )
        return (_rms(x) * (1.0 + w.astype(jnp.float32))).astype(self.dtype)


class Mixer(nn.Module):
    """The contract between a token mixer and everything above it.

    **Fields** (what ``Block`` passes, the same for every mixer): ``cfg``;
    ``layer_type``, the mixer's key in ``MIXERS``; ``causal`` (False: the
    LRA classifier's bidirectional forward); ``mesh`` + cfg.sequence_parallel
    switch the causal parallel forward to token-sharded execution over the
    mesh's sp axis (SURVEY.md P5/P6); ``sp_local``: the caller is ALREADY
    inside a shard_map manual over sp (the pp×sp pipeline body,
    parallel/pipeline_lm.py) and x carries the sp-LOCAL token shard — run
    the sp bodies directly instead of opening a nested shard_map, which
    jax's sdy lowering rejects; ``quant``: "" | "int8" | "int4",
    weight-streamed decode (orion_tpu/quant.py); ``sp_local_kernels``: set
    by the FULL-manual pipeline — the enclosing shard_map is manual over
    every axis, so Mosaic kernels are legal in the sp-local bodies; the
    partial-manual default pins them to the XLA forms. A mixer that cannot
    honour a field asserts so in its forward.

    **Training** — ``__call__(x [B, T, D], mask [B, T] | None) -> [B, T, D]``.

    **Serving** — a mixer that can be served declares its zero state in
    the static :meth:`decode_state` and overrides the entry points below
    (``prefill``, ``prefill_extend``, ``decode_step``; the speculative pair
    ``verify_extend`` / ``advance_verified`` where it is built); what a
    mixer does not override is inherited, and raises. The state is a
    dict of arrays with the batch on axis 0: the planner, the AOT listing
    and the slot engine take its shapes from
    ``eval_shape(init_decode_state)``, and insert / extract / snapshot are
    tree maps over it, so its format is the mixer's own business.

    ``rows_in_place``: True where :meth:`decode_step`, given a row list,
    leaves the state of unlisted rows untouched (bitwise), so the decode
    programs need not select those rows back (``generate._freeze_rows``).
    """

    cfg: ModelConfig
    layer_type: str
    causal: bool = True
    mesh: Optional[Any] = None
    sp_local: bool = False
    quant: str = ""
    sp_local_kernels: bool = False

    rows_in_place = False
    # what the engine asks instead of the layer type's name
    # (``SlotEngine.kv_rows``, ``held_bytes``): the state leaves that are a
    # position-indexed cache, how many rows of it a slot reserves
    # (``cache_rows``) and how many a decode step streams (``cache_rows_read``)
    cache_leaves: Tuple[str, ...] = ()
    # the state leaves that are a short convolution's tail: its last
    # ``width - 1`` input rows, a fixed few KB a slot whatever the prompt
    # (``held_bytes``' ``tail_bytes``)
    tail_leaves: Tuple[str, ...] = ()

    @staticmethod
    def cache_rows(cfg: ModelConfig, layer_type: str) -> int:
        """Cache rows a slot reserves in this layer; 0: no cache."""
        return 0

    @staticmethod
    def cache_is_ring(cfg: ModelConfig, layer_type: str) -> bool:
        """Is that reservation a RING (``cache_rows`` rows written at
        position % ``cache_rows``, of which ``min(position, cache_rows)`` are
        live) and not a cache that grows with the position? The engine
        counts the two kinds apart (``SlotEngine.kv_rows`` / ``ring_rows``)."""
        return False

    @staticmethod
    def cache_rows_read(cfg: ModelConfig, layer_type: str, length: int):
        """Cache rows one decode step streams for an EMITTING slot of
        ``length`` live rows where the backend runs the layer's row-list
        kernel (and nothing for a slot that is not emitting); None: the
        layer has no such kernel, every slot's reservation is read."""
        return None

    # -- serving: the defaults of a train-only mixer --------------------------

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        """The zero decode state for ``batch`` sequences, structured like
        the state :meth:`prefill` returns; ``dtype`` is the cache dtype
        (fp32 accumulators ignore it)."""
        raise NotImplementedError(
            f"layer type {layer_type!r} has a training forward only: no decode "
            "state (a grouped-KV cache, for gated_softmax) is built for it"
        )

    @staticmethod
    def chunk_split(
        cfg: ModelConfig, layer_type: str, state: State, n_steps: int,
        t: Array, donated: bool,
    ) -> Tuple[State, State]:
        """``(held, carried)`` for a decode scan of ``n_steps`` that starts
        at positions ``t`` [B]: the leaves the scan only READS, and the
        leaves it carries and updates. The slot-multiplexed decode programs
        (``generate._scan_chunk``) give :meth:`decode_step` the two merged
        and keep what it returns under the carried names. ``donated`` says
        which program asks: one that holds its carry once
        (``generate._decode_scan_donated_jit``), where a KV cache of GBs
        cannot afford the copy XLA makes of a scan's carry at its entry
        (it does not copy what the scan closes over), or one that returns
        a new carry beside the one it was given. A layer that splits for
        another reason (the linear layers' state, written once a chunk)
        does so in both. Default: everything is carried."""
        return {}, state

    @staticmethod
    def chunk_merge(
        cfg: ModelConfig, layer_type: str, held: State, carried: State,
        live: Array,
    ) -> State:
        """The state after the scan, from :meth:`chunk_split`'s two parts;
        rows outside ``live`` [B] keep their held leaves' bits."""
        return carried

    def _train_only(self):
        raise NotImplementedError(
            f"layer type {self.layer_type!r} does not build this serving "
            "entry point: gated_softmax has a training forward only; "
            "gated_delta, decay_linear, block_sparse, ssm, latent, indexed and "
            "gated_conv serve (prefill, its pieces, the decode step) but have "
            "no speculative verify_extend / advance_verified"
        )

    def prefill(
        self, x: Array, length: Optional[Array] = None
    ) -> Tuple[Array, State]:
        """The parallel forward, also returning the decode state after the
        prompt. ``length``: optional traced REAL prompt length when ``x``
        is right-padded to a bucket (serving's prompt-length bucketing,
        one compile per bucket instead of per novel length); the state
        must come out bitwise-equal to an unpadded prefill of
        ``x[:, :length]``."""
        self._train_only()

    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """One chunked-prefill piece: ``x`` [B, P, D] holds rows
        [offset, offset+P) of the prompt's hidden stream (right-padded —
        ``length`` of them real, both traced), ``state`` is the decode
        state left by the pieces before it. Returns (mixer out for the
        piece rows, advanced state).

        Bitwise contract (the serving engine's in-scan admission,
        orion_tpu/serving/batching.py): when every piece boundary is a
        multiple of the linear-attention chunk, piece-by-piece extension
        reproduces the monolithic :meth:`prefill` EXACTLY on the xla
        backend — real rows' outputs and every state row are
        bitwise-identical, pinned by tests/test_prefill_inscan.py.

        Token-by-token consumption inside the decode scan can NOT deliver
        this contract — a single-row matvec accumulates differently from
        the prefill gemm (measured: kv rows differ at 1e-6 on CPU) — which
        is why chunked prefill is pieces of the parallel forward between
        scan chunks rather than a mask inside the scan body."""
        self._train_only()

    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """x: [B, D] one token; t: int32 absolute position — a scalar
        (whole batch at one position: generate()'s lockstep scan) or a
        per-sequence [B] vector (slot-multiplexed serving: each batch row
        is an independent request at its own position). ``rows``: the
        slot-multiplexed programs' compacted list of the rows live in
        this chunk (``ops.dispatch.decode_state_step``); a mixer with
        ``rows_in_place`` steps only those, the others ignore it."""
        self._train_only()

    def verify_extend(
        self, x: Array, state: State, t: Array
    ) -> Tuple[Array, State]:
        """Self-speculative VERIFY piece for one layer: ``x`` [B, P, D]
        holds the hidden rows of P candidate tokens at positions
        ``t``..``t+P-1`` (``t`` a per-sequence [B] vector). Returns (mixer
        out for every row, the per-token state-update payload for
        :meth:`advance_verified`).

        The bitwise contract — THE one speculative decoding needs — is
        identity with P successive :meth:`decode_step` calls, not with
        prefill: the projections run as one P-row gemm (row-stable: each
        output row's reduction is independent of the batch shape, pinned
        by tests/test_spec_decode.py), while the state-dependent part —
        the (S, z) recurrence, the cache read-modify-write — replays
        decode_step's exact per-token op sequence at the same [B, H, Dh]
        shapes via a P-step inner scan. That is deliberately NOT
        :meth:`prefill_extend`'s chunk-granular gemm fold, which is
        bitwise against monolithic PREFILL but accumulates differently
        from the matvec decode walk (the measured 1e-6 the prefill-piece
        docstring records). Weights still stream once for all P rows —
        the speculative win — only the cheap recurrence stays sequential.

        The state walked inside is a SHADOW advanced by all P tokens and
        is not returned (rejected drafts must never become the carry);
        callers re-apply the accepted prefix via :meth:`advance_verified`."""
        self._train_only()

    def advance_verified(
        self, state: State, upd: State, t: Array, keep: Array
    ) -> State:
        """Clamped state advance after verification: re-apply the first
        ``keep`` (per-sequence, traced) of the P per-token updates
        :meth:`verify_extend` computed, leaving the rest of the state
        BITWISE untouched — rejected drafts are never observable."""
        self._train_only()

    # -- helpers of the mixers with n_heads x head_dim q / k / v / o ----------

    def _setup_qkvo(self, kv_heads: Optional[int] = None):
        """``kv_heads``: how many heads k and v have (default: as many as
        q; fewer is a grouped KV)."""
        cfg = self.cfg
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        dense = _dense_factory(cfg, self.quant, self.mesh)
        self.wq = dense("wq", h * dh)
        self.wk = dense("wk", (kv_heads or h) * dh)
        self.wv = dense("wv", (kv_heads or h) * dh)
        self.wo = dense("wo", cfg.d_model)
        assert cfg.qk_norm in ("none", "projection", "head"), cfg.qk_norm
        if cfg.qk_norm != "none":
            norm = dict(epsilon=cfg.norm_eps, dtype=_dtype(cfg.dtype))
            self.q_norm = nn.RMSNorm(name="q_norm", **norm)
            self.k_norm = nn.RMSNorm(name="k_norm", **norm)

    def _heads(self, x: Array) -> Tuple[Array, Array, Array]:
        """x [..., T, D] (or [..., D]) -> q,k,v [..., H, T, Dh] ([..., H, Dh])."""
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        single = x.ndim == 2  # decode: [B, D]
        q, k, v = self.wq(x), self.wk(x), self.wv(x)
        if cfg.qk_norm == "projection":  # over all heads' columns at once
            q, k = self.q_norm(q), self.k_norm(k)

        def split(y):
            y = y.reshape(*y.shape[:-1], y.shape[-1] // dh, dh)
            if single:
                return y  # [B, H, Dh]
            return jnp.swapaxes(y, -3, -2)  # [B, T, H, Dh] -> [B, H, T, Dh]

        q, k, v = split(q), split(k), split(v)
        if cfg.qk_norm == "head":  # over each head's own width, one weight
            q, k = self.q_norm(q), self.k_norm(k)
        return q, k, v

    def _merge(self, out: Array, single: bool) -> Array:
        if not single:
            out = jnp.swapaxes(out, -3, -2)  # [B, T, H, Dh]
        return self.wo(out.reshape(*out.shape[:-2], -1))

    def _kernel_bh(self, fn, *args):
        return kernel_bh(self.cfg, self.mesh, fn, *args)

    def _sp_active(self) -> bool:
        return (
            self.cfg.sequence_parallel
            and self.causal
            and self.mesh is not None
            and self.mesh.shape.get("sp", 1) > 1
        )


# the mixer files import the names above, so the registry comes last
from orion_tpu.models.mixers.block_sparse import (  # noqa: E402
    BlockSparseAttention,
)
from orion_tpu.models.mixers.decay_linear import (  # noqa: E402
    DecayLinearAttention,
)
from orion_tpu.models.mixers.gated_conv import GatedConv  # noqa: E402
from orion_tpu.models.mixers.gated_delta import GatedDeltaNet  # noqa: E402
from orion_tpu.models.mixers.gated_softmax import (  # noqa: E402
    GatedSoftmaxAttention,
)
from orion_tpu.models.mixers.indexed import IndexedAttention  # noqa: E402
from orion_tpu.models.mixers.latent import LatentAttention  # noqa: E402
from orion_tpu.models.mixers.linear import LinearAttention  # noqa: E402
from orion_tpu.models.mixers.softmax import SoftmaxAttention  # noqa: E402
from orion_tpu.models.mixers.ssm import StateSpace  # noqa: E402

MIXERS = {
    "linear": LinearAttention,
    "softmax": SoftmaxAttention,
    "swa": SoftmaxAttention,
    "gated_delta": GatedDeltaNet,
    "gated_softmax": GatedSoftmaxAttention,
    "decay_linear": DecayLinearAttention,
    "block_sparse": BlockSparseAttention,
    "ssm": StateSpace,
    "latent": LatentAttention,
    "indexed": IndexedAttention,
    "gated_conv": GatedConv,
}
assert set(MIXERS) == set(LAYER_TYPES), (sorted(MIXERS), LAYER_TYPES)

__all__ = [
    "MIXERS", "Mixer", "LinearAttention", "SoftmaxAttention", "GatedDeltaNet",
    "GatedSoftmaxAttention", "DecayLinearAttention", "BlockSparseAttention",
    "StateSpace", "LatentAttention", "IndexedAttention", "GatedConv", "ZeroCentredRMSNorm", "kernel_bh", "whole_array_backend",
]
