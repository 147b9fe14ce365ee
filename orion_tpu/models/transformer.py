"""TransformerLM: decoder LM with a token mixer per layer (models/mixers/:
linear / softmax / sliding-window attention, the gated delta rule, gated
GQA softmax), SwiGLU or GELU MLP, RMSNorm/LayerNorm, tied or untied head.

The reference's model family (BASELINE.json: tiny 2L/128d, 1.3B linear-attn,
7B hybrid swa+linear; the reference checkout was never mounted — SURVEY.md
§0), rebuilt flax-first. Three entry methods per module, all jit-friendly:

- ``__call__(tokens)``      — parallel training forward (chunked linear
  attention / flash softmax via ops dispatch).
- ``prefill(tokens)``       — same forward, additionally returning per-layer
  decode state: linear layers hand back the kv-cumsum state (S, z); softmax
  layers a KV cache; swa layers a ring-buffer window cache.
- ``decode_step(tok, st, t)`` — one-token recurrent step, O(1) state for
  linear layers; designed to sit inside a single ``lax.scan``.

Positional scheme (SURVEY.md M6): learned absolute embeddings at the input
(what the linear layers see — rotating phi-space vectors would break the
kernel trick) + rotary applied inside softmax/swa layers.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import (
    MIXERS,
    ZeroCentredRMSNorm,
    _dense_factory,
    _dtype,
    drawn_in,
    ungated_activation,
)
from orion_tpu.obs import trace as _trace

Array = jax.Array
State = Dict[str, Array]


def _norm(cfg: ModelConfig, name: str):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(
            epsilon=cfg.norm_eps, dtype=_dtype(cfg.dtype), name=name
        )
    if cfg.norm == "rmsnorm_zero":
        return ZeroCentredRMSNorm(
            _dtype(cfg.dtype), _dtype(cfg.param_dtype), name=name
        )
    return nn.LayerNorm(dtype=_dtype(cfg.dtype), name=name)


class MLP(nn.Module):
    cfg: ModelConfig
    quant: str = ""
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cfg = self.cfg
        h = cfg.resolved_mlp_hidden
        dense = _dense_factory(cfg, self.quant, self.mesh)
        if cfg.mlp == "swiglu":
            gate = dense("gate", h)(x)
            up = dense("up", h)(x)
            y = jax.nn.silu(gate) * up
        else:
            y = ungated_activation(cfg.mlp)(dense("up", h)(x))
        return dense("down", cfg.d_model)(y)


class Block(nn.Module):
    """Residual block: x + attn(norm(x)); x + mlp(norm(x)) (pre-norm), or
    with ``cfg.norm_placement == "post"`` x + norm(attn(x)); x +
    norm(mlp(x)), or with ``"sandwich"`` x + post(attn(norm(x))); x +
    post(mlp(norm(x))); ``attn`` is the layer type's token mixer
    (models/mixers/).

    ``use_moe`` swaps the dense MLP for the routed-expert MoEMLP
    (models/moe.py, ep-sharded); same name "mlp" so one sharding rule set
    covers both layouts. ``mixer_only`` (``cfg.mixer_only`` names the
    blocks): the block is its first residual step alone, with no ``norm2``,
    no ``mlp`` and no ``post_norm2`` among its parameters; every method
    returns after the mixer's residual add."""

    cfg: ModelConfig
    layer_type: str
    causal: bool = True
    mesh: Optional[Any] = None
    sp_local: bool = False
    use_moe: bool = False
    quant: str = ""
    sp_local_kernels: bool = False
    mixer_only: bool = False

    def setup(self):
        self.norm1 = _norm(self.cfg, "norm1")
        self.attn = MIXERS[self.layer_type](
            self.cfg, self.layer_type, self.causal, self.mesh,
            self.sp_local, quant=self.quant,
            sp_local_kernels=self.sp_local_kernels, name="attn"
        )
        self.drop = nn.Dropout(self.cfg.dropout)
        if self.cfg.norm_placement == "sandwich":
            self.post_norm1 = _norm(self.cfg, "post_norm1")
        if self.mixer_only:
            assert not self.use_moe, "a block that is a mixer alone has no experts"
            return
        self.norm2 = _norm(self.cfg, "norm2")
        if self.cfg.norm_placement == "sandwich":
            self.post_norm2 = _norm(self.cfg, "post_norm2")
        if self.use_moe:
            from orion_tpu.models.moe import MoEMLP

            self.mlp = MoEMLP(
                self.cfg, mesh=self.mesh, quant=self.quant, name="mlp"
            )
        else:
            self.mlp = MLP(
                self.cfg, quant=self.quant, mesh=self.mesh, name="mlp"
            )

    def _sublayer(self, norm, f, x):
        """``f(norm(x))``, or ``norm(f(x))`` where the configuration
        normalises a sublayer's output, or ``post(f(norm(x)))`` where it
        does both (``"sandwich"``: ``post_norm1`` after the mixer,
        ``post_norm2`` after the MLP); ``f`` may return ``(y, extra)``."""
        place = self.cfg.norm_placement
        if place == "pre":
            return f(norm(x))
        if place == "sandwich":
            y = f(norm(x))
            norm = self.post_norm1 if norm is self.norm1 else self.post_norm2
        else:
            assert place == "post", place
            y = f(x)
        return (norm(y[0]),) + tuple(y[1:]) if isinstance(y, tuple) else norm(y)

    def _branch(self, y):
        """A residual branch's output, scaled by ``cfg.residual_scale``
        where the configuration sets one (1.0: the program as it was)."""
        a = self.cfg.residual_scale
        return y if a == 1.0 else (y.astype(jnp.float32) * a).astype(y.dtype)

    def _mlp_residual(self, x, live=None):
        """``live``: the serving methods' row mask, which a routed-expert MLP
        takes (models/moe.py); a dense MLP has no use for it. A block that is
        a mixer alone ends before this step."""
        if self.mixer_only:
            return x
        mlp = self.mlp if live is None or not self.use_moe else (
            lambda y: self.mlp(y, live)
        )
        return x + self._branch(self._sublayer(self.norm2, mlp, x))

    def __call__(self, x, mask=None, deterministic=True):
        x = x + self.drop(
            self._branch(self._sublayer(self.norm1, lambda y: self.attn(y, mask), x)),
            deterministic=deterministic,
        )
        if self.mixer_only:
            return x
        x = x + self.drop(
            self._branch(self._sublayer(self.norm2, self.mlp, x)),
            deterministic=deterministic,
        )
        return x

    def _real_rows(self, x, length):
        """``[B, T]``: the rows of a right-padded prompt or piece that are
        real, for a routed-expert MLP; None where no length says."""
        if length is None or not self.use_moe:
            return None
        return jnp.broadcast_to(jnp.arange(x.shape[-2]) < length, x.shape[:-1])

    def prefill(self, x, length=None):
        h, state = self._sublayer(
            self.norm1, lambda y: self.attn.prefill(y, length), x
        )
        return self._mlp_residual(
            x + self._branch(h), self._real_rows(x, length)
        ), state

    def prefill_extend(self, x, state, offset, length):
        h, state = self._sublayer(
            self.norm1,
            lambda y: self.attn.prefill_extend(y, state, offset, length), x,
        )
        return self._mlp_residual(
            x + self._branch(h), self._real_rows(x, length)
        ), state

    def prefill_extend_group(self, x, state, offsets, lengths):
        """``prefill_extend`` for the pieces of ``x.shape[0]`` sequences at
        once, ``state`` a list of their batch-1 states: each attends alone,
        at its own offset and length, and the feed-forward layer takes all
        their rows as one batch."""
        hs, rows = [], []
        for i, st in enumerate(state):
            h, st = self._sublayer(
                self.norm1,
                lambda y, i=i, st=st: self.attn.prefill_extend(
                    y, st, offsets[i], lengths[i]
                ),
                x[i:i + 1],
            )
            hs.append(h)
            rows.append(st)
        live = None
        if self.use_moe:
            live = jnp.arange(x.shape[-2]) < lengths[:, None]
        return self._mlp_residual(
            x + self._branch(jnp.concatenate(hs, axis=0)), live
        ), rows

    def decode_step(self, x, state, t, rows=None, live=None):
        h, state = self._sublayer(
            self.norm1, lambda y: self.attn.decode_step(y, state, t, rows), x
        )
        return self._mlp_residual(x + self._branch(h), live), state

    def verify_extend(self, x, state, t):
        h, upd = self._sublayer(
            self.norm1, lambda y: self.attn.verify_extend(y, state, t), x
        )
        return self._mlp_residual(x + self._branch(h)), upd


# What a rematted block keeps of its forward for its backward, by
# ``checkpoint_name``: the names of its mixer's layer type and, for a block
# with experts, those under "moe". Everything without a listed name is
# computed again, as under no policy, and a block that lists nothing is the
# program it was. A name belongs here when its recompute is dear and its bytes
# are few (PERF.md section 6, PR 60):
REMAT_KEEPS: Dict[str, Tuple[str, ...]] = {
    # the flash forward's output and rows (ops/pallas/flash_attention.py::
    # _flash_lse_vjp_fwd). ``gated_softmax`` attends causally over the WHOLE
    # sequence: at T 8,192 its forward costs 65 ms a GB held. A sliding
    # window's costs a third of that and the dense hybrids have spent the
    # memory on ``remat_skip``, so ``swa`` / ``softmax`` blocks carry the same
    # names through the same kernel and keep neither
    "gated_softmax": ("flash_out", "flash_lse"),
    # the integer lists of the experts' counting sort where the rows move by
    # list (models/moe.py::_held_rows_ffn): a few MB for a fifth of the layer
    "moe": ("moe_lists",),
}


def _keeps(names: Tuple[str, ...]):
    """``save_only_these_names(*names)`` that also counts what it keeps, once
    an equation and trace (``obs.trace.compile_totals``'s
    ``remat_kept_residuals`` / ``remat_kept_bytes``)."""
    listed = jax.checkpoint_policies.save_only_these_names(*names)

    def policy(prim, *avals, **params):
        keep = listed(prim, *avals, **params)
        if keep:
            _trace.remat_kept(sum(a.size * a.dtype.itemsize for a in avals))
        return keep

    return policy


@functools.lru_cache(maxsize=None)
def _rematted(layer_type: str, use_moe: bool):
    """``Block`` under ``nn.remat`` with the names policy of what it is made
    of: one lifted class a (mixer, feed-forward) kind, not one a model."""
    names = REMAT_KEEPS.get(layer_type, ()) + (REMAT_KEEPS["moe"] if use_moe else ())
    return nn.remat(Block, static_argnums=(3,), policy=_keeps(names))


class TransformerLM(nn.Module):
    """Decoder LM over token ids; see module docstring for the 3 methods."""

    cfg: ModelConfig
    mesh: Optional[Any] = None
    quant: str = ""  # "" | "int8": weight-streamed decode (orion_tpu/quant.py)

    def setup(self):
        cfg = self.cfg
        pdt = _dtype(cfg.param_dtype)
        if self.quant:  # int8 table in both quant modes (head fidelity)
            from orion_tpu.quant import Int8Embed

            self.embed = Int8Embed(cfg.vocab_size, cfg.d_model)
        else:
            init = (
                {} if cfg.embed_init_std is None
                else {"embedding_init": drawn_in(
                    cfg, nn.initializers.normal(cfg.embed_init_std))}
            )
            self.embed = nn.Embed(
                cfg.vocab_size, cfg.d_model, param_dtype=pdt, **init
            )
        if cfg.pos_embed == "learned":
            self.pos_embed = nn.Embed(
                cfg.max_seq_len, cfg.d_model, param_dtype=pdt
            )
        else:
            assert cfg.pos_embed == "none", cfg.pos_embed
        # remat_skip: the last K blocks keep their activations (configs.py)
        first_remat = (cfg.n_layers - max(0, cfg.remat_skip)) if cfg.remat else 0
        self.blocks = [
            (_rematted(lt, cfg.moe_at(i)) if i < first_remat else Block)(
                cfg, lt, True, self.mesh,
                quant=self.quant, name=f"block_{i}", **cfg.block_form(i),
            )
            for i, lt in enumerate(cfg.resolved_layer_types)
        ]
        self.final_norm = _norm(cfg, "final_norm")
        if not cfg.tie_embeddings:
            if self.quant:
                self.lm_head_kernel_q = self.param(
                    "lm_head_kernel_q",
                    nn.initializers.zeros_init(),
                    (cfg.d_model, cfg.vocab_size),
                    jnp.int8,
                )
                self.lm_head_kernel_s = self.param(
                    "lm_head_kernel_s",
                    nn.initializers.ones_init(),
                    (cfg.vocab_size,),
                    jnp.float32,
                )
            else:
                self.lm_head_kernel = self.param(
                    "lm_head_kernel",
                    drawn_in(cfg, nn.initializers.lecun_normal()),
                    (cfg.d_model, cfg.vocab_size),
                    pdt,
                )

    def _embed(self, tokens: Array, positions: Array) -> Array:
        """The input embedding, times ``cfg.embed_scale`` where set."""
        x = self._embed_rows(tokens, positions)
        a = self.cfg.embed_scale
        return x if a == 1.0 else (x.astype(jnp.float32) * a).astype(x.dtype)

    def _final(self, x: Array) -> Array:
        """The head's input: the final norm, times ``cfg.logit_scale``."""
        x = self.final_norm(x)
        a = self.cfg.logit_scale
        return x if a == 1.0 else (x.astype(jnp.float32) * a).astype(x.dtype)

    def _embed_rows(self, tokens: Array, positions: Array) -> Array:
        if self.mesh is None or self.quant:
            # quant mode skips the fsdp replicated-constraint trick below:
            # the int8 table is 4x smaller and the sharding rules store
            # embedding_q REPLICATED (parallel/sharding.py), so the gather
            # never touches an fsdp-sharded table
            x = self.embed(tokens)
            if self.cfg.pos_embed == "learned":
                x = x + self.pos_embed(positions)
            return x.astype(_dtype(self.cfg.dtype))
        # FSDP-style lookup: the tables are *stored* feature-sharded over
        # fsdp (parallel/sharding.py), but gather/scatter on a sharded table
        # makes GSPMD fall back to involuntary full rematerialization in
        # both directions (observed in the dp2/fsdp2/tp2 dryrun; VERDICT r1
        # weak #3). Constraining a transient replicated copy turns that into
        # one clean all-gather per step (reduce-scatter in the backward) —
        # the same collective fsdp already pays for every matmul param.
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P(None, None))
        wt = jax.lax.with_sharding_constraint(self.embed.embedding, rep)
        x = jnp.take(wt, tokens, axis=0)
        if self.cfg.pos_embed == "learned":
            wp = jax.lax.with_sharding_constraint(self.pos_embed.embedding, rep)
            x = x + jnp.take(wp, positions, axis=0)
        x = x.astype(_dtype(self.cfg.dtype))
        if x.ndim == 3:
            # sequence-parallel runs keep activations token-sharded over sp
            # from the very first layer: the qkv projections then already
            # produce the shard_map boundary's P(batch, tp, sp, None) layout,
            # so GSPMD never has to fall back to an involuntary full
            # rematerialization to re-shard [B, H, T, D] (VERDICT r1 weak #3)
            sp = (
                "sp"
                if self.cfg.sequence_parallel
                and self.mesh.shape.get("sp", 1) > 1
                and x.shape[1] % self.mesh.shape["sp"] == 0
                else None
            )
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, P(("dp", "fsdp"), sp, None))
            )
        return x

    def _head(self, x: Array) -> Array:
        """final_norm + head matmul (prefill/decode call this on raw block
        output)."""
        return self._head_matmul(self._final(x))

    def _head_matmul(self, x: Array) -> Array:
        """Logits in fp32, but the matmul itself runs in the compute dtype
        with fp32 MXU accumulation — a pure-fp32 [.., D]x[D, V] head matmul
        is ~4x slower on TPU for no useful precision gain."""
        cdt = _dtype(self.cfg.dtype)
        if self.quant:
            if self.cfg.tie_embeddings:
                return self.embed.attend(x, cdt)
            y = jnp.einsum(
                "...d,dv->...v",
                x.astype(cdt),
                self.lm_head_kernel_q.astype(cdt),
                preferred_element_type=jnp.float32,
            )
            return y * self.lm_head_kernel_s
        if self.cfg.tie_embeddings:
            w = self.embed.embedding.astype(cdt)  # [V, D]
            return jnp.einsum(
                "...d,vd->...v", x.astype(cdt), w,
                preferred_element_type=jnp.float32,
            )
        w = self.lm_head_kernel.astype(cdt)  # [D, V]
        return jnp.einsum(
            "...d,dv->...v", x.astype(cdt), w,
            preferred_element_type=jnp.float32,
        )

    def __call__(self, tokens: Array, deterministic: bool = True) -> Array:
        """tokens [B, T] -> logits [B, T, V] (fp32)."""
        return self._head_matmul(self.features(tokens, deterministic))

    def features(self, tokens: Array, deterministic: bool = True) -> Array:
        """tokens [B, T] -> final-normed hidden states [B, T, D], i.e. the
        head matmul's input. The fused-CE training path (ops/fused_ce.py)
        consumes this and applies the head inside its chunked scan, so the
        full [B, T, V] fp32 logits never materialize; __call__ is exactly
        ``_head_matmul(features(tokens))``."""
        t = tokens.shape[-1]
        x = self._embed(tokens, jnp.arange(t))
        for blk in self.blocks:
            x = blk(x, None, deterministic)
        return self._final(x)

    def head_weight(self, params) -> Tuple[Array, bool]:
        """(head weight array, w_is_vd) for ops/fused_ce.py — the tied
        embedding [V, D] or the untied lm_head_kernel [D, V]. Static method
        in spirit: reads the param pytree, no module state."""
        p = params["params"]
        if self.cfg.tie_embeddings:
            return p["embed"]["embedding"], True
        return p["lm_head_kernel"], False

    def _prefill_trunk(
        self, tokens: Array, length: Optional[Array] = None
    ) -> Tuple[Array, List[State]]:
        """Shared embed + per-block state-collecting forward -> (x, states).
        ``length``: traced real prompt length when ``tokens`` is padded to
        a bucket (see Mixer.prefill)."""
        t = tokens.shape[-1]
        x = self._embed(tokens, jnp.arange(t))
        states = []
        for blk in self.blocks:
            x, st = blk.prefill(x, length)
            states.append(st)
        return x, states

    def prefill(self, tokens: Array, length: Optional[Array] = None) -> Tuple[Array, List[State]]:
        """tokens [B, T] -> (logits [B, T, V], per-layer decode states)."""
        x, states = self._prefill_trunk(tokens, length)
        return self._head(x), states

    def prefill_last(
        self, tokens: Array, length: Optional[Array] = None
    ) -> Tuple[Array, List[State]]:
        """prefill, but the head matmul runs on the LAST position only ->
        (logits [B, V], states). Generation needs nothing else, and the
        full-prompt head is the difference between a [B, T, V] fp32 tensor
        (4.3GB at T=32k) and a [B, V] row — long-prompt serving fits
        because of this (generate.py uses it; ``prefill`` keeps the full
        contract for parity tests and scoring). With ``length`` (bucketed
        prefill), the head runs on the last REAL position ``length - 1``,
        not the padded end."""
        x, states = self._prefill_trunk(tokens, length)
        if length is not None:
            last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            return self._head(last)[:, 0], states
        return self._head(x[:, -1:, :])[:, 0], states

    def decode_step(
        self, token: Array, states: List[State], t: Array,
        rows: Optional[Any] = None, live: Optional[Array] = None,
    ) -> Tuple[Array, List[State]]:
        """token [B] -> (logits [B, V], updated states). t: scalar position,
        or [B] per-slot positions; ``rows``: see Mixer.decode_step; ``live``
        [B]: the rows whose token counts (the slot-multiplexed programs'
        emitting slots), for the layers that route rows (models/moe.py)."""
        x = self._embed(token, t)
        new_states = []
        for blk, st in zip(self.blocks, states):
            x, st = blk.decode_step(x, st, t, rows, live)
            new_states.append(st)
        return self._head(x), new_states

    # -- self-speculative decode (ISSUE 13) -----------------------------------

    def draft_step(
        self, token: Array, lin_states: List[State], t: Array
    ) -> Tuple[Array, List[State]]:
        """One DRAFT step: the model's own global-linear sublayers run as
        a cheap standalone decoder — embed -> only the ``linear`` blocks
        of ``cfg.resolved_layer_types`` (softmax/swa blocks are skipped
        entirely: no cache read, no cache write, no window attend) ->
        final norm -> head. ``lin_states`` is the linear layers' (S, z)
        sublist in layer order — the SAME O(1) carry rows the full model
        threads, so the draft runs ahead k tokens at a fraction of the
        full forward's cost with zero extra weights and no cache growth.
        The caller walks a functional shadow copy and discards it after
        verification: draft quality affects only the ACCEPTANCE RATE
        (speed), never the emitted tokens — verification re-samples from
        the full model's logits (see generate.decode_batched_spec_round)."""
        x = self._embed(token, t)
        new_states: List[State] = []
        it = iter(lin_states)
        for blk, lt in zip(self.blocks, self.cfg.resolved_layer_types):
            if lt != "linear":
                continue
            x, st = blk.decode_step(x, next(it), t)
            new_states.append(st)
        return self._head(x), new_states

    def verify_step(
        self, tokens: Array, states: List[State], t: Array
    ) -> Tuple[Array, List[List[State]]]:
        """Speculative VERIFY: ``tokens`` [B, P] are the pending token
        plus P-1 drafted continuations per slot, ``t`` [B] their start
        positions. Returns (full-model logits at EVERY fed position
        [B, P, V], the per-layer update payloads for
        :meth:`advance_verified_states`).

        Logits come out BITWISE identical to feeding the P tokens
        through P successive :meth:`decode_step` calls (the per-layer
        contract: Mixer.verify_extend), while every weight matmul —
        qkv/out projections, MLP, head — runs ONCE as a P-row gemm. On
        weight-bandwidth-bound hardware that is the speculative win: one
        weight stream verifies k tokens; only the O(1)-state recurrence
        (elementwise, no weights) stays sequential."""
        p = tokens.shape[-1]
        pos = t[:, None] + jnp.arange(p)[None, :]
        x = self._embed(tokens, pos)
        upds: List[State] = []
        for blk, st in zip(self.blocks, states):
            x, upd = blk.verify_extend(x, st, t)
            upds.append(upd)
        return self._head(x), upds

    def advance_verified_states(
        self, states: List[State], upds: List[State], t: Array, keep: Array
    ) -> List[State]:
        """Apply the first ``keep`` (per-sequence) verified tokens' state
        updates from :meth:`verify_step`'s payload onto ``states`` —
        rows' rejected suffixes leave the state bitwise untouched (see
        Mixer.advance_verified)."""
        return [
            blk.attn.advance_verified(st, upd, t, keep)
            for blk, st, upd in zip(self.blocks, states, upds)
        ]

    def prefill_extend_step(
        self, tokens: Array, states: List[State], offset: Array, length: Array
    ) -> Tuple[Array, List[State]]:
        """One chunked-prefill PIECE at the model level: ``tokens`` [B, P]
        are prompt rows [offset, offset + P) (right-padded — ``length`` of
        them real, both traced), ``states`` the decode state left by the
        pieces before. Returns (logits of the last REAL row [B, V], the
        advanced states) — after the final piece, exactly what
        ``prefill_last`` hands the first-token sampler, bitwise (the
        serving engine's in-scan admission; see Mixer.prefill_extend
        and each mixer's own for the per-layer-type contract). Positions are clipped, not
        sliced: the batched stage runs this for non-prefilling slots too
        and discards their rows, so garbage offsets must stay in-range
        rather than clamp-shift."""
        p = tokens.shape[-1]
        pos = jnp.clip(offset + jnp.arange(p), 0, self.cfg.max_seq_len - 1)
        x = self._embed(tokens, pos)
        new_states = []
        for blk, st in zip(self.blocks, states):
            x, st = blk.prefill_extend(x, st, offset, length)
            new_states.append(st)
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(length - 1, 0), 1, axis=1
        )
        return self._head(last)[:, 0], new_states

    def prefill_extend_group(
        self, tokens: Array, states: List[State], offsets: Array, lengths: Array
    ) -> Tuple[Array, List[State]]:
        """``prefill_extend_step`` for one piece of each of ``G`` sequences,
        ``tokens`` [G, P] with ``offsets`` and ``lengths`` [G] (a length of 0
        leaves its sequence's state as it was), ``states`` a list of the G
        sequences' batch-1 decode states: every sequence's mixers run as
        they do alone, the feed-forward layers see the G pieces' rows
        together (a routed-expert layer then streams its experts once for
        all of them). Returns (the last real rows' logits [G, V], the G
        advanced states)."""
        p = tokens.shape[-1]
        pos = jnp.clip(
            offsets[:, None] + jnp.arange(p), 0, self.cfg.max_seq_len - 1
        )
        x = jnp.concatenate(
            [self._embed(tokens[i:i + 1], pos[i]) for i in range(tokens.shape[0])]
        )
        layers = []
        for k, blk in enumerate(self.blocks):
            x, sts = blk.prefill_extend_group(
                x, [st[k] for st in states], offsets, lengths
            )
            layers.append(sts)
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
        )
        return self._head(last)[:, 0], [list(sts) for sts in zip(*layers)]


def linear_layer_indices(cfg: ModelConfig) -> Tuple[int, ...]:
    """Indices of the global-linear layers — the model's built-in draft
    (``TransformerLM.draft_step``); the speculative engine slices these
    rows out of the batched state to thread the draft's (S, z) carry."""
    return tuple(
        i for i, lt in enumerate(cfg.resolved_layer_types) if lt == "linear"
    )


def snapshot_decode_state(states: List[State]) -> List[State]:
    """O(1) snapshot of the per-layer decode state for the serving rewind
    path (orion_tpu/serving/batching.py). jax arrays are immutable, so a
    snapshot only needs fresh *containers* — the rewind target must not see
    dicts that a later chunk's bookkeeping mutated in place. No device copy
    happens (the decode chunks never donate their state buffers)."""
    return jax.tree.map(lambda x: x, states)


@jax.jit
def _per_slot_finite(states: List[State]) -> Array:
    b = jax.tree.leaves(states)[0].shape[0]
    acc = jnp.ones((b,), bool)
    for leaf in jax.tree.leaves(states):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            acc = jnp.logical_and(
                acc,
                jnp.all(jnp.isfinite(leaf.reshape(leaf.shape[0], -1)), axis=1),
            )
    return acc


def decode_state_finite_per_slot(states: List[State]) -> Array:
    """Per-SEQUENCE all-finite probe over the (S, z)/KV/ring decode state:
    [B] bool vector, one entry per slot of the batched decode state, one
    fused reduction per floating leaf (integer leaves, the cache slot
    bookkeeping, are skipped). The slot-multiplexed serving engine
    (orion_tpu/serving/batching.py) probes per slot so one poisoned slot
    walks the degradation ladder for THAT request only while co-resident
    slots keep streaming. Returns the DEVICE vector: ONE device reduction
    and one host transfer per chunk regardless of slot count, at the
    engine's designated probe point (analysis rule ``decode-host-sync``)."""
    return _per_slot_finite(states)


def insert_decode_slot(
    states: List[State], slot_states: List[State], i: Array
) -> List[State]:
    """Write a single sequence's decode state (batch dim 1 — the output
    of a solo prefill) into row ``i`` of the batched per-layer state
    pytree. Row writes are ``.at[i].set`` scatters, so under jit the
    whole admission costs one fused update per leaf; everything about the
    slot's previous occupant is overwritten."""
    return jax.tree.map(
        lambda full, one: full.at[i].set(one[0]), states, slot_states
    )


def extract_decode_slot(states: List[State], i: Array) -> List[State]:
    """Row ``i`` of the batched decode state as a batch-of-1 state pytree —
    the inverse of :func:`insert_decode_slot`. This is the SUSPEND half of
    the durable-session round trip (serving/session_store.py): the row is
    pulled to host at a chunk boundary and later re-inserted at the saved
    position and rng-fold index, bitwise-identical to having stayed
    resident (insert(extract(i)) is identity by construction — only ever
    called on a state the per-slot finite probe just passed; the ladder's
    re-prefill rung still rebuilds from tokens, since a POISONED row is
    exactly what it must not reuse)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0), states
    )


def init_decode_state(
    cfg: ModelConfig, batch_size: int, dtype: Any = None
) -> List[State]:
    """Zero decode state matching prefill's structure (for prompt-less
    generation): each layer's ``Mixer.decode_state`` — linear layers fp32
    (S, z); softmax: [B,H,Smax,Dh] KV cache; swa: [B,H,W,Dh] ring cache; a
    train-only mixer raises NotImplementedError."""
    dt = dtype or _dtype(cfg.dtype)
    return [
        MIXERS[lt].decode_state(cfg, lt, batch_size, dt)
        for lt in cfg.resolved_layer_types
    ]


__all__ = [
    "TransformerLM", "Block", "MLP", "init_decode_state",
    "snapshot_decode_state", "decode_state_finite_per_slot",
    "insert_decode_slot", "extract_decode_slot", "linear_layer_indices",
]
