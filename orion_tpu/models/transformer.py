"""TransformerLM: decoder LM with per-layer linear / softmax / sliding-window
attention, SwiGLU or GELU MLP, RMSNorm/LayerNorm, tied or untied head.

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

from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import TRAIN_ONLY_LAYER_TYPES, ModelConfig
from orion_tpu.ops.dispatch import decode_state_step
from orion_tpu.ops.feature_maps import make_feature_map
from orion_tpu.ops.linear_attention import (
    linear_attention,
    linear_attention_noncausal,
    recurrent_step,
)
from orion_tpu.ops.rotary import apply_rotary, apply_rotary_at, rotary_freqs
from orion_tpu.ops.softmax_attention import cached_attention, softmax_attention

Array = jax.Array
State = Dict[str, Array]

# remat_policy name -> jax.checkpoint policy; the single definition shared
# by the model's per-block remat and the pipeline adapter (pipeline_lm.py)
REMAT_POLICIES = {
    "full": None,  # save only block boundaries, recompute all
    "dots": jax.checkpoint_policies.checkpoint_dots,
}


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _qdense_factory(quant: str, dt, mesh=None):
    """Dense-layer factory for the weight-streamed decode modes, or None
    for full-precision. "int8": every matmul int8. "int4": matmul weights
    nibble-packed int4, while embedding/head (token-distribution-critical,
    table shared) and MoE expert stacks stay int8 — the mixed scheme
    VERDICT r3 #5 names. ``mesh`` reaches Int4Dense so its fused-kernel
    gate reflects the MODEL's mesh, not the host's device count
    (ADVICE r4: a single-device model on a multi-device host must not
    silently lose the kernel)."""
    if not quant:
        return None
    from orion_tpu.quant import Int4Dense, Int8Dense

    if quant == "int4":
        return lambda n, feats: Int4Dense(feats, dtype=dt, mesh=mesh, name=n)
    assert quant == "int8", quant
    return lambda n, feats: Int8Dense(feats, dtype=dt, name=n)


def _norm(cfg: ModelConfig, name: str):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(dtype=_dtype(cfg.dtype), name=name)
    if cfg.norm == "rmsnorm_zero":
        from orion_tpu.models.gated_mixers import ZeroCentredRMSNorm

        return ZeroCentredRMSNorm(
            _dtype(cfg.dtype), _dtype(cfg.param_dtype), name=name
        )
    return nn.LayerNorm(dtype=_dtype(cfg.dtype), name=name)


def kernel_bh(cfg: ModelConfig, mesh, fn, *args):
    """Kernel dispatch for per-(batch, head)-parallel attention: on a
    GSPMD mesh whose data axes split, a Mosaic kernel must be
    manualized (XLA cannot auto-partition tpu_custom_call) — shard_map
    over (dp, fsdp, tp) via parallel/kernel_shard.py; everywhere else
    the call goes straight through."""
    from orion_tpu.ops.dispatch import resolve
    from orion_tpu.parallel.kernel_shard import needs_manual, shard_map_bh

    b = resolve(cfg.backend)
    if needs_manual(mesh, b):
        # vma ON for real Mosaic (its lowering requires it in a
        # partial-manual region), OFF for interpret kernels (which
        # cannot trace under the check) — kernel_shard.py docstring
        return shard_map_bh(mesh, fn, *args, check_vma=(b != "pallas_interpret"))
    return fn(*args)


class Attention(nn.Module):
    """One attention layer of type 'linear' | 'softmax' | 'swa'.

    ``mesh`` + cfg.sequence_parallel switches the causal parallel forward to
    token-sharded execution over the mesh's sp axis (SURVEY.md P5/P6).

    ``sp_local``: the caller is ALREADY inside a shard_map manual over sp
    (the pp×sp pipeline body, parallel/pipeline_lm.py) and x carries the
    sp-LOCAL token shard — run the sp bodies (sp_linear_attention_local /
    ring_attention_local) directly instead of opening a nested shard_map,
    which jax's sdy lowering rejects."""

    cfg: ModelConfig
    layer_type: str
    causal: bool = True
    mesh: Optional[Any] = None
    sp_local: bool = False
    quant: str = ""  # "" | "int8": weight-streamed decode (orion_tpu/quant.py)
    # set by the FULL-manual pipeline (parallel/pipeline_lm.py): the
    # enclosing shard_map is manual over every axis, so Mosaic kernels are
    # legal in the sp-local bodies; the partial-manual default pins them
    # to the XLA forms
    sp_local_kernels: bool = False

    def setup(self):
        cfg = self.cfg
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        dense = lambda n, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=dt, param_dtype=pdt, name=n
        )
        qdense = _qdense_factory(self.quant, dt, self.mesh) or dense
        self.wq = qdense("wq", h * dh)
        self.wk = qdense("wk", h * dh)
        self.wv = qdense("wv", h * dh)
        self.wo = qdense("wo", cfg.d_model)
        if self.layer_type == "linear":
            if cfg.feature_map == "learnable":
                self.phi_proj = dense("phi_proj", dh)
                self._phi = lambda x: jax.nn.elu(x) + 1.0
            elif cfg.feature_map == "favor":
                self.favor_w = self.param(
                    "favor_proj",
                    lambda rng: _favor_proj_init(rng, dh),
                )
                self._phi = None
            else:
                self._phi = make_feature_map(cfg.feature_map)
        else:
            # rotary angle table, a trace-time constant
            self.freqs = rotary_freqs(dh, cfg.max_seq_len)

    # -- shared projections -------------------------------------------------

    def _heads(self, x: Array) -> Tuple[Array, Array, Array]:
        """x [..., T, D] (or [..., D]) -> q,k,v [..., H, T, Dh] ([..., H, Dh])."""
        cfg = self.cfg
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        single = x.ndim == 2  # decode: [B, D]
        q, k, v = self.wq(x), self.wk(x), self.wv(x)

        def split(y):
            if single:
                return y.reshape(*y.shape[:-1], h, dh)  # [B, H, Dh]
            y = y.reshape(*y.shape[:-1], h, dh)  # [B, T, H, Dh]
            return jnp.swapaxes(y, -3, -2)  # [B, H, T, Dh]

        return split(q), split(k), split(v)

    def _phi_map(self, x: Array) -> Array:
        cfg = self.cfg
        if cfg.feature_map == "learnable":
            return self._phi(self.phi_proj(x))
        if cfg.feature_map == "favor":
            w = jax.lax.stop_gradient(self.favor_w)  # fixed random features
            xf = x.astype(jnp.float32) / (x.shape[-1] ** 0.25)
            proj = jnp.einsum("...d,md->...m", xf, w)
            sq = 0.5 * jnp.sum(xf * xf, axis=-1, keepdims=True)
            return (jnp.exp(proj - sq) / jnp.sqrt(w.shape[0])).astype(x.dtype)
        return self._phi(x)

    def _merge(self, out: Array, single: bool) -> Array:
        if not single:
            out = jnp.swapaxes(out, -3, -2)  # [B, T, H, Dh]
        return self.wo(out.reshape(*out.shape[:-2], -1))

    def _kernel_bh(self, fn, *args):
        return kernel_bh(self.cfg, self.mesh, fn, *args)

    # -- parallel forward ---------------------------------------------------

    def _sp_active(self) -> bool:
        return (
            self.cfg.sequence_parallel
            and self.causal
            and self.mesh is not None
            and self.mesh.shape.get("sp", 1) > 1
        )

    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        cfg = self.cfg
        q, k, v = self._heads(x)
        t = x.shape[-2]
        sp = self._sp_active()
        if sp:
            assert t % self.mesh.shape["sp"] == 0, (t, dict(self.mesh.shape))
        if self.layer_type == "linear":
            qf, kf = self._phi_map(q), self._phi_map(k)
            if self.sp_local and self.causal:
                from orion_tpu.parallel.sequence import sp_linear_attention_local

                # In the partial-manual pipeline the XLA chunked form is
                # STRUCTURAL, not a fallback: jax rejects Mosaic kernels in
                # any partial-manual region ("cannot be automatically
                # partitioned"), and that pipeline leaves dp/fsdp/tp to
                # GSPMD by design. The FULL-manual pipeline
                # (pipeline_lm.py full_manual) sets sp_local_kernels and
                # the requested backend goes through — every other
                # fully-manual composition already carries kernels
                # (kernel_shard.py; sequence.py/ring.py).
                out = sp_linear_attention_local(
                    qf, kf, v,
                    backend=cfg.backend if self.sp_local_kernels else "xla",
                    chunk=cfg.chunk,
                )
            elif sp:
                from orion_tpu.parallel.sequence import sp_linear_attention

                out = sp_linear_attention(
                    qf, kf, v, self.mesh, backend=cfg.backend, chunk=cfg.chunk
                )
            elif self.causal:
                out = self._kernel_bh(
                    lambda a, b, c: linear_attention(
                        a, b, c, backend=cfg.backend, chunk=cfg.chunk
                    ),
                    qf, kf, v,
                )
            else:
                km = None if mask is None else mask[:, None, :]
                out = linear_attention_noncausal(qf, kf, v, mask=km)
        else:
            if self.sp_local:
                # x is the sp-LOCAL token shard: rotary needs the global
                # positions of this shard's rows
                i = jax.lax.axis_index("sp")
                ang = jax.lax.dynamic_slice_in_dim(self.freqs, i * t, t, axis=0)
            else:
                ang = self.freqs[:t]
            q = apply_rotary(q, ang)
            k = apply_rotary(k, ang)
            window = cfg.window if self.layer_type == "swa" else None
            # striped = the load-balanced ring (parallel/ring.py): full-
            # causal softmax only; swa keeps the contiguous ring (striping
            # a window loses its locality)
            striped = cfg.ring_striped and window is None
            if self.sp_local and self.causal:
                from orion_tpu.ops.dispatch import resolve
                from orion_tpu.parallel.ring import (
                    ring_attention_local,
                    swa_halo_attention_local,
                )

                # sp_local_kernels (full-manual pipeline): kernel-backed
                # forms — halo for swa; full-causal softmax gets flash
                # blocks only when cfg.ring_striped is set (the contiguous
                # ring body is XLA regardless of backend). Partial-manual
                # pipelines always use the XLA bodies.
                b = resolve(cfg.backend) if self.sp_local_kernels else "xla"
                if window is not None and b.startswith("pallas"):
                    out = swa_halo_attention_local(
                        q, k, v, window=window,
                        interpret=(b == "pallas_interpret"),
                    )
                else:
                    out = ring_attention_local(
                        q, k, v, causal=True, window=window,
                        striped=striped, backend=b,
                    )
            elif sp:
                from orion_tpu.ops.dispatch import resolve
                from orion_tpu.parallel.ring import (
                    ring_attention,
                    swa_halo_attention,
                )

                if window is not None and resolve(cfg.backend).startswith(
                    "pallas"
                ):
                    # swa under sp with kernels: halo exchange (O(h)
                    # ppermutes + flash blocks at static q_offset) beats
                    # the n-step ring — ring.py::swa_halo_attention_local
                    out = swa_halo_attention(
                        q, k, v, self.mesh, window=window,
                        backend=cfg.backend,
                    )
                else:
                    out = ring_attention(
                        q, k, v, self.mesh, causal=True, window=window,
                        striped=striped, backend=cfg.backend,
                    )
            elif mask is None and self.causal:
                out = self._kernel_bh(
                    lambda a, b, c: softmax_attention(
                        a, b, c, causal=True, window=window,
                        backend=cfg.backend,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                    ),
                    q, k, v,
                )
            else:
                # masked / bidirectional (classifier): mask shapes don't fit
                # the [B, H, ...] manualization — stays on the GSPMD path
                # (xla backend; LRA configs are xla anyway)
                am = None if mask is None else mask[:, None, None, :]
                out = softmax_attention(
                    q, k, v, causal=self.causal, window=window,
                    mask=am, backend=cfg.backend,
                )
        return self._merge(out, single=False)

    # -- prefill: forward + decode state ------------------------------------

    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        """``length``: optional traced per-call REAL prompt length when
        ``x`` is right-padded to a bucket (serving's prompt-length
        bucketing, one compile per bucket instead of per novel length).
        The decode state must come out bitwise-equal to an unpadded
        prefill of ``x[:, :length]``:

        - linear — pad positions' phi(k)/v rows are zeroed BEFORE the
          kv-cumsum, so S/z accumulate only real contributions (adding
          exact zeros is bitwise-exact) and every real position's output
          is untouched (causal: it never sees later rows).
        - softmax — the padded KV rows land at cache slots >= length,
          which decode never reads: step t overwrites slot t before
          attending and masks slots > t (see decode_step), so no masking
          is needed here.
        - swa — the ring cache is built from the last ``window`` REAL
          positions via a traced gather/scatter
          (:func:`_swa_cache_from_prefill_dynamic`)."""
        cfg = self.cfg
        q, k, v = self._heads(x)
        t = x.shape[-2]
        if self.layer_type == "linear":
            qf, kf = self._phi_map(q), self._phi_map(k)
            if length is not None:
                # where (not multiply): 0*nan from a degenerate feature
                # map must not poison the masked state
                real = (jnp.arange(t) < length)[None, None, :, None]
                kf = jnp.where(real, kf, jnp.zeros_like(kf))
                v = jnp.where(real, v, jnp.zeros_like(v))
            out, (s, z) = self._kernel_bh(
                lambda a, b, c: linear_attention(
                    a, b, c, backend=cfg.backend, chunk=cfg.chunk,
                    return_state=True,
                ),
                qf, kf, v,
            )
            state = {"s": s, "z": z}
        else:
            ang = self.freqs[:t]
            qr = apply_rotary(q, ang)
            kr = apply_rotary(k, ang)
            if self.layer_type == "swa":
                out = self._kernel_bh(
                    lambda a, b, c: softmax_attention(
                        a, b, c, causal=True, window=cfg.window,
                        backend=cfg.backend,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                    ),
                    qr, kr, v,
                )
                if length is not None:
                    state = _swa_cache_from_prefill_dynamic(
                        kr, v, length, cfg.window
                    )
                else:
                    state = _swa_cache_from_prefill(kr, v, t, cfg.window)
            else:
                out = self._kernel_bh(
                    lambda a, b, c: softmax_attention(
                        a, b, c, causal=True, backend=cfg.backend,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                    ),
                    qr, kr, v,
                )
                smax = cfg.max_seq_len
                pad = ((0, 0), (0, 0), (0, smax - t), (0, 0))
                state = {"k": jnp.pad(kr, pad), "v": jnp.pad(v, pad)}
        return self._merge(out, single=False), state

    # -- chunked prefill: advance decode state by one prompt piece -----------

    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """One chunked-prefill piece: ``x`` [B, P, D] holds rows
        [offset, offset+P) of the prompt's hidden stream (right-padded —
        ``length`` of them real, both traced), ``state`` is the decode
        state left by the pieces before it. Returns (attn out for the
        piece rows, advanced state).

        Bitwise contract (the serving engine's in-scan admission,
        orion_tpu/serving/batching.py): when every piece boundary is a
        multiple of the linear-attention chunk, piece-by-piece extension
        reproduces the monolithic :meth:`prefill` EXACTLY on the xla
        backend — real rows' outputs, (S, z), KV rows, and ring rows are
        bitwise-identical, pinned by tests/test_prefill_inscan.py. The
        ingredients:

        - linear — the numerator state AND the z normalizer thread through
          ``linear_attention(initial_state=...)``'s chunk-granular scan (a
          strict left fold — splitting at chunk boundaries replays the
          identical op sequence; ops/linear_attention.py return_zcum).
          Pad rows' phi(k)/v are zeroed exactly like bucketed prefill.
        - softmax — per-token projections and rotary are row-stable, so
          the piece's KV rows are written into the cache (masked
          read-modify-write) and the piece's queries attend over the
          WHOLE cache under an offset causal mask; masked lanes are exact
          zeros after softmax, so key-axis padding to the cache capacity
          is reduction-neutral.
        - swa — the piece attends over a [W + P] context assembled from
          the ring (position-ordered gather) plus its own rows; the ring
          is then rebuilt from the last W real positions, sourcing each
          row from the piece or the previous ring.

        Token-by-token consumption inside the decode scan can NOT deliver
        this contract — a single-row matvec accumulates differently from
        the prefill gemm (measured: kv rows differ at 1e-6 on CPU) — which
        is why chunked prefill is pieces of the parallel forward between
        scan chunks rather than a mask inside the scan body.
        """
        from orion_tpu.ops.softmax_attention import softmax_attention_xla

        cfg = self.cfg
        q, k, v = self._heads(x)
        p = x.shape[-2]
        real = (jnp.arange(p) < length)[None, None, :, None]
        if self.layer_type == "linear":
            qf, kf = self._phi_map(q), self._phi_map(k)
            # where (not multiply): 0*nan from a degenerate feature map
            # must not poison the masked state (same as bucketed prefill)
            kf = jnp.where(real, kf, jnp.zeros_like(kf))
            vm = jnp.where(real, v, jnp.zeros_like(v))
            out, (s, z) = linear_attention(
                qf, kf, vm, backend=cfg.backend, chunk=cfg.chunk,
                initial_state=(state["s"], state["z"]), return_state=True,
            )
            new_state = {"s": s, "z": z}
        else:
            # clipped gather, not dynamic_slice: a garbage offset (the
            # batched stage computes pieces for NON-prefilling rows too,
            # then discards them) must not clamp-shift anything; real rows
            # always sit at in-range positions
            pos = jnp.clip(offset + jnp.arange(p), 0, self.freqs.shape[0] - 1)
            ang = jnp.take(self.freqs, pos, axis=0)
            qr = apply_rotary(q, ang)
            kr = apply_rotary(k, ang)
            if self.layer_type == "swa":
                out, new_state = self._swa_extend(
                    qr, kr, v, state, offset, length, cfg.window
                )
            else:
                kc = _window_write(state["k"], kr, offset, real)
                vc = _window_write(state["v"], v, offset, real)
                row = jnp.arange(p)[:, None] + offset
                col = jnp.arange(kc.shape[-2])[None, :]
                out = softmax_attention_xla(
                    qr, kc, vc, causal=False, mask=row >= col
                )
                new_state = {"k": kc, "v": vc}
        return self._merge(out, single=False), new_state

    def _swa_extend(
        self, qr: Array, kr: Array, v: Array, state: State,
        offset: Array, length: Array, window: int,
    ) -> Tuple[Array, State]:
        """Sliding-window piece attention + ring-buffer advance (see
        :meth:`prefill_extend`). The context is the W positions before the
        piece (gathered from the ring in position order) plus the piece's
        own rows; negative/garbage positions are masked, never read."""
        from orion_tpu.ops.softmax_attention import softmax_attention_xla

        p = qr.shape[-2]
        w = window
        pos_prev = offset - w + jnp.arange(w)  # may be < 0 (masked below)
        slots_prev = pos_prev % w
        kprev = jnp.take(state["k"], slots_prev, axis=2)
        vprev = jnp.take(state["v"], slots_prev, axis=2)
        kctx = jnp.concatenate(
            [kprev, kr.astype(state["k"].dtype)], axis=2
        )
        vctx = jnp.concatenate([vprev, v.astype(state["v"].dtype)], axis=2)
        row = (jnp.arange(p)[:, None] + offset)
        colpos = jnp.concatenate(
            [pos_prev, offset + jnp.arange(p)]
        )[None, :]
        m = (row >= colpos) & (row - colpos < w) & (colpos >= 0)
        out = softmax_attention_xla(qr, kctx, vctx, causal=False, mask=m)
        # rebuild the ring as the last W positions before offset+length:
        # rows from this piece where they cover, the previous ring where
        # they don't; slots (pos % W) of W consecutive positions are a
        # permutation, so the scatter is collision-free and deterministic
        t_cur = offset + length
        pos_new = t_cur - w + jnp.arange(w)
        slots_new = pos_new % w
        take = jnp.clip(pos_new - offset, 0, p - 1)
        sel = (pos_new >= offset)[None, None, :, None]
        kc = state["k"].at[:, :, slots_new, :].set(jnp.where(
            sel,
            jnp.take(kr.astype(state["k"].dtype), take, axis=2),
            jnp.take(state["k"], slots_new, axis=2),
        ))
        vc = state["v"].at[:, :, slots_new, :].set(jnp.where(
            sel,
            jnp.take(v.astype(state["v"].dtype), take, axis=2),
            jnp.take(state["v"], slots_new, axis=2),
        ))
        return out, {"k": kc, "v": vc}

    # -- speculative verify: batched re-walk of k decode steps ----------------

    def verify_extend(
        self, x: Array, state: State, t: Array
    ) -> Tuple[Array, State]:
        """Self-speculative VERIFY piece for one attention layer: ``x``
        [B, P, D] holds the hidden rows of P candidate tokens at
        positions ``t``..``t+P-1`` (``t`` a per-sequence [B] vector).
        Returns (attn out for every row, the per-token state-update
        payload for :meth:`advance_verified`).

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

        The returned state is a SHADOW advanced by all P tokens; callers
        discard it (rejected drafts must never become the carry) and
        re-apply the accepted prefix via :meth:`advance_verified`."""
        cfg = self.cfg
        q, k, v = self._heads(x)  # [B, H, P, Dh]
        to_steps = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
        if self.layer_type == "linear":
            qf, kf = self._phi_map(q), self._phi_map(k)

            def body(carry, qkv):
                qj, kj, vj = qkv  # [B, H, Dh] — decode_step's shapes
                out, carry = recurrent_step(qj, kj, vj, carry)
                return carry, out

            _, outs = jax.lax.scan(
                body, (state["s"], state["z"]),
                (to_steps(qf), to_steps(kf), to_steps(v)),
            )
            out = jnp.moveaxis(outs, 0, 2)  # [B, H, P, Dh]
            upd = {"k": kf, "v": v}
        else:
            cap = state["k"].shape[-2]
            b_idx = jnp.arange(x.shape[0])

            def body(carry, qkv):
                kc, vc, tj = carry
                qj, kj, vj = qkv
                # the decode_step per-seq path, one token at a time
                qr = apply_rotary_at(qj, self.freqs, tj[:, None])
                kr = apply_rotary_at(kj, self.freqs, tj[:, None])
                slot = tj % cap if self.layer_type == "swa" else tj
                kc = kc.at[b_idx, :, slot, :].set(kr.astype(kc.dtype))
                vc = vc.at[b_idx, :, slot, :].set(vj.astype(vc.dtype))
                valid = jnp.arange(cap)[None, None, :] <= tj[:, None, None]
                outj = cached_attention(qr, kc, vc, valid)
                return (kc, vc, tj + 1), (outj, kr)

            _, (outs, krs) = jax.lax.scan(
                body, (state["k"], state["v"], t),
                (to_steps(q), to_steps(k), to_steps(v)),
            )
            out = jnp.moveaxis(outs, 0, 2)
            upd = {"k": jnp.moveaxis(krs, 0, 2), "v": v}
        return self._merge(out, single=False), upd

    def advance_verified(
        self, state: State, upd: State, t: Array, keep: Array
    ) -> State:
        """Clamped state advance after verification: re-apply the first
        ``keep`` (per-sequence, traced) of the P per-token updates
        :meth:`verify_extend` computed, leaving the rest of the state
        BITWISE untouched — rejected drafts are never observable.

        - linear — replay recurrent_step's fp32 rank-1 adds in sequence,
          each behind a where-select on ``j < keep``: elementwise ops on
          identical operands, so the kept prefix is bitwise the
          sequential walk and a skipped add leaves (S, z) exactly as it
          was.
        - softmax/swa — one masked batched scatter: token j writes its
          (rotary'd) row at its own slot when ``j < keep``, else writes
          the CURRENT cache row back (a bitwise no-op). P consecutive
          positions hit P distinct slots (the engine enforces
          spec depth + 1 <= window), so the scatter equals the
          sequential writes."""
        p = upd["v"].shape[2]
        if self.layer_type == "linear":
            kf = upd["k"].astype(jnp.float32)
            vf = upd["v"].astype(jnp.float32)
            m = keep.reshape(keep.shape + (1,) * 3)

            def body(carry, inp):
                s, z = carry
                kj, vj, j = inp
                s2 = s + kj[..., :, None] * vj[..., None, :]
                z2 = z + kj
                take = j < m
                return (
                    jnp.where(take, s2, s),
                    jnp.where(take[..., 0], z2, z),
                ), None

            (s, z), _ = jax.lax.scan(
                body, (state["s"], state["z"]),
                (jnp.moveaxis(kf, 2, 0), jnp.moveaxis(vf, 2, 0),
                 jnp.arange(p)),
            )
            return {"s": s, "z": z}
        cap = state["k"].shape[-2]
        pos = t[:, None] + jnp.arange(p)[None, :]  # [B, P]
        # UNclipped for softmax, exactly like decode_step's slot = t: an
        # overshoot position past the cache capacity must DROP (jax
        # out-of-bounds scatter semantics), not clamp-write — bitwise
        # with the sequential walk either way
        slot = pos % cap if self.layer_type == "swa" else pos
        b_idx = jnp.arange(t.shape[0])[:, None]
        m = (jnp.arange(p)[None, :] < keep[:, None])[:, :, None, None]
        cur_k = state["k"][b_idx, :, slot, :]  # [B, P, H, Dh]
        cur_v = state["v"][b_idx, :, slot, :]
        new_k = jnp.where(
            m, jnp.moveaxis(upd["k"], 2, 1).astype(state["k"].dtype), cur_k
        )
        new_v = jnp.where(
            m, jnp.moveaxis(upd["v"], 2, 1).astype(state["v"].dtype), cur_v
        )
        return {
            "k": state["k"].at[b_idx, :, slot, :].set(new_k),
            "v": state["v"].at[b_idx, :, slot, :].set(new_v),
        }

    # -- one-token decode ---------------------------------------------------

    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """x: [B, D] one token; t: int32 absolute position — a scalar
        (whole batch at one position: generate()'s lockstep scan) or a
        per-sequence [B] vector (slot-multiplexed serving: each batch row
        is an independent request at its own position). ``rows``: the
        slot-multiplexed programs' compacted list of the rows live in
        this chunk (``ops.dispatch.decode_state_step``): under a Pallas
        backend a linear layer then steps only those rows' (S, z), in
        place, and returns the others untouched; softmax/swa layers
        ignore it."""
        cfg = self.cfg
        t = jnp.asarray(t)
        per_seq = t.ndim == 1
        q, k, v = self._heads(x)  # [B, H, Dh]
        if self.layer_type == "linear":
            qf, kf = self._phi_map(q), self._phi_map(k)
            out, (s, z) = decode_state_step(
                qf, kf, v, (state["s"], state["z"]), rows, backend=cfg.backend
            )
            new_state = {"s": s, "z": z}
        else:
            # per-seq positions: angles gather [B, 1, Dh/2] broadcasts over
            # heads the way the scalar gather's [Dh/2] row does
            pos = t[:, None] if per_seq else t
            qr = apply_rotary_at(q, self.freqs, pos)
            kr = apply_rotary_at(k, self.freqs, pos)
            cap = state["k"].shape[-2]  # window W or max_seq_len
            slot = t % cap if self.layer_type == "swa" else t
            if per_seq:
                # one scatter row per sequence at its own slot
                b_idx = jnp.arange(x.shape[0])
                kc = state["k"].at[b_idx, :, slot, :].set(
                    kr.astype(state["k"].dtype)
                )
                vc = state["v"].at[b_idx, :, slot, :].set(
                    v.astype(state["v"].dtype)
                )
                valid = jnp.arange(cap)[None, None, :] <= t[:, None, None]
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(
                    state["k"], kr[:, :, None, :].astype(state["k"].dtype), slot, axis=2
                )
                vc = jax.lax.dynamic_update_slice_in_dim(
                    state["v"], v[:, :, None, :].astype(state["v"].dtype), slot, axis=2
                )
                # ring slots hold positions (t-W, t] once warm; before that,
                # slots (t, W) are still unwritten — in both cases exactly the
                # slots with index <= t are valid (softmax is permutation-
                # invariant over keys, so rotation needs no unrotation).
                valid = (jnp.arange(cap) <= t)[None, None, :]
            out = cached_attention(qr, kc, vc, valid)
            new_state = {"k": kc, "v": vc}
        return self._merge(out, single=True), new_state


def _favor_proj_init(rng: Array, dh: int) -> Array:
    from orion_tpu.ops.feature_maps import _orthogonal_gaussian

    return _orthogonal_gaussian(rng, dh, dh)


def _window_write(
    cache: Array, rows: Array, offset: Array, real: Array
) -> Array:
    """Masked read-modify-write of a [B, H, P, Dh] row block into the full
    KV cache at traced ``offset``: pad rows (``real`` False) keep whatever
    the cache held, so a partial final piece never clobbers slots the
    decode's ``slot <= t`` rule may later expose. Scatter at clipped
    per-row positions, NOT dynamic_update_slice: an out-of-range offset
    (pieces are computed for non-prefilling rows too, then discarded)
    would make dynamic_update_slice clamp the window and silently shift
    every row; here pad/garbage rows write the cache's own value back —
    a bitwise no-op even when clipping collides their positions."""
    p = rows.shape[-2]
    pos = jnp.clip(offset + jnp.arange(p), 0, cache.shape[-2] - 1)
    cur = jnp.take(cache, pos, axis=2)
    new = jnp.where(real, rows.astype(cache.dtype), cur)
    return cache.at[:, :, pos, :].set(new)


def _swa_cache_from_prefill(kr: Array, v: Array, t: int, window: int) -> State:
    """Build the ring-buffer cache from the last ``window`` prompt tokens,
    each at slot (position % window); unwritten slots stay zero (they are
    masked by the slot <= t rule in decode_step)."""
    b, h, _, dh = kr.shape
    start = max(0, t - window)
    n = t - start
    positions = jnp.arange(start, t)
    slots = positions % window
    kc = jnp.zeros((b, h, window, dh), kr.dtype).at[:, :, slots, :].set(
        kr[:, :, start:t, :]
    )
    vc = jnp.zeros((b, h, window, v.shape[-1]), v.dtype).at[:, :, slots, :].set(
        v[:, :, start:t, :]
    )
    del n
    return {"k": kc, "v": vc}


def _swa_cache_from_prefill_dynamic(
    kr: Array, v: Array, length: Array, window: int
) -> State:
    """:func:`_swa_cache_from_prefill` with a TRACED real length (bucketed
    prefill pads the prompt, so the ring must be built from the last
    ``window`` positions BEFORE ``length``, not before the padded end).
    Positions < 0 (prompt shorter than the window) write a clipped-gather
    row into their slot; those slots are never read — decode's
    ``slot <= t`` rule excludes a slot until the step that overwrites it
    (see decode_step) — so the garbage is harmless and the readable
    entries are bitwise-identical to the static builder's."""
    b, h, t_pad, dh = kr.shape
    positions = length - window + jnp.arange(window)  # [W], may be < 0
    slots = positions % window
    safe = jnp.clip(positions, 0, t_pad - 1)
    kc = jnp.zeros((b, h, window, dh), kr.dtype).at[:, :, slots, :].set(
        jnp.take(kr, safe, axis=2)
    )
    vc = jnp.zeros((b, h, window, v.shape[-1]), v.dtype).at[:, :, slots, :].set(
        jnp.take(v, safe, axis=2)
    )
    return {"k": kc, "v": vc}


class MLP(nn.Module):
    cfg: ModelConfig
    quant: str = ""
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        h = cfg.resolved_mlp_hidden
        dense = _qdense_factory(self.quant, dt, self.mesh) or (
            lambda n, feats: nn.Dense(
                feats, use_bias=False, dtype=dt, param_dtype=pdt, name=n
            )
        )
        if cfg.mlp == "swiglu":
            gate = dense("gate", h)(x)
            up = dense("up", h)(x)
            y = jax.nn.silu(gate) * up
        else:
            y = jax.nn.gelu(dense("up", h)(x))
        return dense("down", cfg.d_model)(y)


class Block(nn.Module):
    """Pre-norm residual block: x + attn(norm(x)); x + mlp(norm(x)).

    ``use_moe`` swaps the dense MLP for the routed-expert MoEMLP
    (models/moe.py, ep-sharded); same name "mlp" so one sharding rule set
    covers both layouts."""

    cfg: ModelConfig
    layer_type: str
    causal: bool = True
    mesh: Optional[Any] = None
    sp_local: bool = False
    use_moe: bool = False
    quant: str = ""
    sp_local_kernels: bool = False

    def setup(self):
        self.norm1 = _norm(self.cfg, "norm1")
        if self.layer_type in TRAIN_ONLY_LAYER_TYPES:
            from orion_tpu.models.gated_mixers import MIXERS

            assert self.causal and not self.sp_local and not self.quant
            self.attn = MIXERS[self.layer_type](
                self.cfg, mesh=self.mesh, name="attn"
            )
        else:
            self.attn = Attention(
                self.cfg, self.layer_type, self.causal, self.mesh,
                self.sp_local, quant=self.quant,
                sp_local_kernels=self.sp_local_kernels, name="attn"
            )
        self.norm2 = _norm(self.cfg, "norm2")
        if self.use_moe:
            from orion_tpu.models.moe import MoEMLP

            self.mlp = MoEMLP(
                self.cfg, mesh=self.mesh, quant=self.quant, name="mlp"
            )
        else:
            self.mlp = MLP(
                self.cfg, quant=self.quant, mesh=self.mesh, name="mlp"
            )
        self.drop = nn.Dropout(self.cfg.dropout)

    def __call__(self, x, mask=None, deterministic=True):
        x = x + self.drop(self.attn(self.norm1(x), mask), deterministic=deterministic)
        x = x + self.drop(self.mlp(self.norm2(x)), deterministic=deterministic)
        return x

    def prefill(self, x, length=None):
        h, state = self.attn.prefill(self.norm1(x), length)
        x = x + h
        x = x + self.mlp(self.norm2(x))
        return x, state

    def prefill_extend(self, x, state, offset, length):
        h, state = self.attn.prefill_extend(
            self.norm1(x), state, offset, length
        )
        x = x + h
        x = x + self.mlp(self.norm2(x))
        return x, state

    def decode_step(self, x, state, t, rows=None):
        h, state = self.attn.decode_step(self.norm1(x), state, t, rows)
        x = x + h
        x = x + self.mlp(self.norm2(x))
        return x, state

    def verify_extend(self, x, state, t):
        h, upd = self.attn.verify_extend(self.norm1(x), state, t)
        x = x + h
        x = x + self.mlp(self.norm2(x))
        return x, upd


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
            self.embed = nn.Embed(cfg.vocab_size, cfg.d_model, param_dtype=pdt)
        if cfg.pos_embed == "learned":
            self.pos_embed = nn.Embed(
                cfg.max_seq_len, cfg.d_model, param_dtype=pdt
            )
        else:
            assert cfg.pos_embed == "none", cfg.pos_embed
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(
                Block, static_argnums=(3,), policy=REMAT_POLICIES[cfg.remat_policy]
            )
        # remat_skip: the last K blocks keep their activations (configs.py)
        first_remat = cfg.n_layers - max(0, cfg.remat_skip)
        self.blocks = [
            (block_cls if i < first_remat else Block)(
                cfg, lt, True, self.mesh,
                use_moe=cfg.moe_at(i), quant=self.quant, name=f"block_{i}",
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
                    nn.initializers.lecun_normal(),
                    (cfg.d_model, cfg.vocab_size),
                    pdt,
                )

    def _embed(self, tokens: Array, positions: Array) -> Array:
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
        return self._head_matmul(self.final_norm(x))

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
        return self.final_norm(x)

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
        a bucket (see Attention.prefill)."""
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
        rows: Optional[Any] = None,
    ) -> Tuple[Array, List[State]]:
        """token [B] -> (logits [B, V], updated states). t: scalar position,
        or [B] per-slot positions; ``rows``: see Attention.decode_step."""
        x = self._embed(token, t)
        new_states = []
        for blk, st in zip(self.blocks, states):
            x, st = blk.decode_step(x, st, t, rows)
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
        contract: Attention.verify_extend), while every weight matmul —
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
        Attention.advance_verified)."""
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
        serving engine's in-scan admission; see Attention.prefill_extend
        for the per-layer-type contract). Positions are clipped, not
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


def linear_layer_indices(cfg: ModelConfig) -> Tuple[int, ...]:
    """Indices of the global-linear layers — the model's built-in draft
    (``TransformerLM.draft_step``); the speculative engine slices these
    rows out of the batched state to thread the draft's (S, z) carry."""
    return tuple(
        i for i, lt in enumerate(cfg.resolved_layer_types) if lt == "linear"
    )


def snapshot_decode_state(states: List[State]) -> List[State]:
    """O(1) snapshot of the per-layer decode state for the serving rewind
    path (orion_tpu/serving/session.py). jax arrays are immutable, so a
    snapshot only needs fresh *containers* — the rewind target must not see
    dicts that a later chunk's bookkeeping mutated in place. No device copy
    happens (the decode chunks never donate their state buffers)."""
    return jax.tree.map(lambda x: x, states)


@jax.jit
def _all_finite(states: List[State]) -> Array:
    acc = jnp.bool_(True)
    for leaf in jax.tree.leaves(states):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            acc = jnp.logical_and(acc, jnp.all(jnp.isfinite(leaf)))
    return acc


def decode_state_finite(states: List[State]) -> Array:
    """Cheap jitted all-finite probe over the (S, z)/KV/ring decode state:
    one fused reduction per floating leaf, ANDed to a scalar bool on
    device. Integer leaves (cache slot bookkeeping) are skipped. Returns
    the DEVICE scalar — the caller decides where to sync it to host
    (serving's designated probe point, see analysis rule
    ``decode-host-sync``)."""
    return _all_finite(states)


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
    """Per-SEQUENCE all-finite probe: [B] bool vector, one entry per slot
    of the batched decode state. The slot-multiplexed serving engine
    (orion_tpu/serving/batching.py) replaces the global scalar probe with
    this so one poisoned slot walks the degradation ladder for THAT
    request only while co-resident slots keep streaming. Still ONE device
    reduction and one host transfer per chunk regardless of slot count."""
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
    generation). Linear layers: fp32 (S, z); softmax: [B,H,Smax,Dh] KV cache;
    swa: [B,H,W,Dh] ring cache."""
    dt = dtype or _dtype(cfg.dtype)
    h, dh = cfg.n_heads, cfg.resolved_head_dim
    b = batch_size
    states: List[State] = []
    for lt in cfg.resolved_layer_types:
        if lt in TRAIN_ONLY_LAYER_TYPES:
            raise NotImplementedError(
                f"layer type {lt!r} has a training forward only: no decode "
                "state (delta-rule state, conv state, grouped-KV cache) is "
                "built for it"
            )
        if lt == "linear":
            states.append(
                {
                    "s": jnp.zeros((b, h, dh, dh), jnp.float32),
                    "z": jnp.zeros((b, h, dh), jnp.float32),
                }
            )
        else:
            cap = cfg.window if lt == "swa" else cfg.max_seq_len
            states.append(
                {
                    "k": jnp.zeros((b, h, cap, dh), dt),
                    "v": jnp.zeros((b, h, cap, dh), dt),
                }
            )
    return states


__all__ = [
    "TransformerLM", "Attention", "Block", "MLP", "init_decode_state",
    "snapshot_decode_state", "decode_state_finite",
    "decode_state_finite_per_slot", "insert_decode_slot",
    "extract_decode_slot", "linear_layer_indices",
]
