"""Mixture-of-Experts MLP with expert parallelism over an ``ep`` mesh axis.

TPU-first formulation (GShard/Switch style): routing is expressed as
einsums against a dense dispatch/combine tensor, so the whole layer is
static-shaped matmuls the MXU can tile — no gather/scatter, no dynamic
shapes, no host round-trips. Expert FFN weights live STACKED on a leading
expert axis (``[E, d, h]``) and shard over the mesh's ``ep`` axis
(parallel/sharding.py); the dispatched activations are constrained to
``P('ep', ...)`` so GSPMD materializes the token exchange as an
all_to_all-class collective over ICI rather than replicating activations.

Reference counterpart: none in BASELINE.json's config list (the reference
checkout was never mounted — SURVEY.md §0); the driver's multi-chip
contract names ``ep`` shardings explicitly, so expert parallelism is part
of the framework's required parallelism vocabulary.

Dispatch is GROUPED (GShard §3.2's local groups): tokens are split into
groups of ``moe_group_size`` consecutive tokens of the same batch row, and
capacity is enforced per group. This keeps the dispatch tensor at
``N·E·C = N·cf·k·S`` elements instead of the flat formulation's
``N²·cf·k/E`` (1.3 GB at the 1.3B config's 32k-token batches), and makes
two properties structural rather than statistical:

- causality: a token can only be evicted by EARLIER tokens of its own row
  (in-group cumsum order), never by future tokens — appending tokens never
  changes earlier positions' outputs;
- batch independence: rows never compete for the same capacity slots.

Recurrent decode matches the parallel forward exactly whenever the
parallel pass drops nothing (capacity factor high enough for the routing
pattern); a prompt token the parallel/prefill pass drops is still expert-
processed by decode, so under drops the two paths differ by design —
inference should raise ``moe_capacity_factor`` rather than mimic training
-time drops.

Routing semantics (jit-friendly, all static shapes):

- router logits/probs computed in fp32;
- top-k (k static, default 1 = Switch) chosen greedily slot by slot;
- per-group-per-expert capacity ``C = ceil(cf·k·S/E)``; capacity positions
  assigned token-major (see ``top_k_routing``) so eviction only ever comes
  from the past, for every k; tokens beyond capacity are dropped (their
  FFN branch contributes 0, the residual stream carries them unchanged);
- combine weights renormalized over the chosen k experts;
- load-balance aux loss (Switch: ``E·Σ_e f_e·P_e``) and router z-loss,
  pre-weighted and sown into the ``"losses"`` collection — the trainer's
  loss adds every leaf of that collection (training/trainer.py::lm_loss).

Decode (``x`` rank-2, one token per row) uses one group with C = B so no
token is ever dropped at decode time — exactness there beats the memory
saving.

``moe_dropless=True`` switches to a sort-based dispatch (``_dropless``):
tokens grouped by expert (counting-sort permutation, no bitonic argsort)
+ ``jax.lax.ragged_dot`` — no capacity, no drops, no train/serve
asymmetry. On ep meshes ``_dropless_ep`` shards the experts: each shard
serves its local experts out of a rotated-sort prefix under a static row
budget and the outputs meet in one psum (drops only past the budget,
counted in "moe_stats", never silent).

One chip's share of an expert-parallel layer (``moe_router_width`` wider
than ``n_experts``): ``_dropless_held`` routes over the router's whole
published width, computes the part of the result that the ``n_experts``
experts held here give (ids from ``moe_expert_offset``; ``_held_rows_ffn``,
the body an ep shard of ``_dropless_ep_gmm`` runs) and leaves out what
the absent experts would add — their chips compute that, and no code here
stands in for them. ``moe_shared_hidden`` adds a shared expert scaled by
``sigmoid(w . x)`` (as it is where ``moe_shared_gated`` is off) to whichever
routed path ran. The held path counts its rows into "moe_stats"
(``rows_routed``, ``rows_held``, ``rows_max_expert``). ``moe_score:
"sigmoid"`` scores each expert ``sigmoid(x W_r)`` instead of the softmax
over the router's width; either way the top-k are renormalised over the k
chosen (over their sum ``+ moe_gate_eps`` where a family publishes one) and
multiplied by ``moe_route_scale``. ``moe_route_bias`` adds a
per-expert fp32 buffer ``router_bias`` to the scores for the CHOICE of the
top-k alone: the weights are the chosen experts' scores without it (forward
only: the balancing rule that would move the buffer is not built).

An expert takes one of two FORMS, by ``cfg.mlp`` (:func:`expert_form`): three
matrices with a gate, ``down(silu(gate x) * up x)`` ("swiglu"), or two without,
``down(act(up x))`` ("gelu"; "relu2", the squared ReLU): two grouped products
a layer and not three. Routed experts, the shared expert and every dispatch
path take either. ``moe_latent`` > 0 puts the routed experts in a LATENT of
that width (dropless layers of one device): ``latent_down`` (``d_model ->
moe_latent``) before them and ``latent_up`` after their weighted sum, both
under the ``moe_latent`` scope, no bias, norm or activation; the expert
stacks are ``[E, moe_latent, h]`` and ``[E, h, moe_latent]`` and everything
buffer-sized on the held-rows path (the rows taken into the buffer, the
combine's gather) is that wide; the router and the shared expert read the
block's own input. A share of such a layer up-projects its own partial sum:
the projection is linear, so the shares' outputs add to the whole layer's.

The SERVING methods hand the layer ``live`` (``[B]`` at a decode step, ``[B,
T]`` in a prompt piece): a row outside it (a slot that is not emitting, a
piece's padding) routes nowhere and counts nowhere. Every dropless layer
that one chip holds without an exchange honours it (:func:`masks_rows`:
a share of the experts or all of them, whatever ``moe_router_width`` says),
and does so on the held-rows path, the one form that can leave a row out:
it then runs its grouped product only over the tiles that hold a row, so a
buffer that holds every pair the router can send here (``moe_ep_buffer >= router width /
experts held``: ``dropless_overflow`` 0 by construction) costs what the held
rows cost, and counts the visits (``tiles_live``, ``experts_live``). The
product's row tile and output block come from the call's shapes
(:func:`serve_tiles`). :func:`stats_vector` is what the decode programs sum a
boundary.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import drawn_in, drawn_kernel_init, ungated_activation
from orion_tpu.utils.profiling import scope

Array = jax.Array


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def masks_rows(cfg: ModelConfig, quant: str = "", mesh: Any = None) -> bool:
    """Do this model's MoE layers honour the serving methods' ``live`` row
    mask and count their rows (``MoEMLP._dropless_held``)? The dropless
    layer of ONE device does, whether it holds a share of its router's
    experts or all of them, and whichever form its experts take (three
    matrices with a gate or two without: :func:`expert_form`); a capacity
    layer, an int8 one and a layer spread over a mesh have no such form. The
    serving programs ask this to know whether to hand ``live`` over and sum
    the counters (generate.py)."""
    return bool(
        cfg.n_experts and cfg.moe_dropless and not quant
        and (mesh is None or mesh.devices.size == 1)
    )


def expert_form(cfg: ModelConfig):
    """(the names of an expert's matrices, its activation) by ``cfg.mlp``:
    gate, up, down with ``silu(gate) * up`` ("swiglu"), or up, down with the
    activation on ``up`` alone ("gelu", "relu2")."""
    if cfg.mlp == "swiglu":
        return ("gate", "up", "down"), jax.nn.silu
    return ("up", "down"), ungated_activation(cfg.mlp)


def _expert_init(in_axis: int = -2):
    """Per-expert lecun-normal over (in, out), expert dim as batch axis —
    matches nn.Dense's default kernel init applied expert-wise."""
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "truncated_normal", in_axis=in_axis, out_axis=-1,
        batch_axis=(0,),
    )


def top_k_choice(
    probs: Array, k: int, select: Optional[Array] = None, eps: float = 0.0
):
    """probs [N, E] fp32 -> (ids [N, k] int32, gates [N, k] fp32): greedy
    top-k expert choice (slot s = argmax with slots <s masked out), gates
    renormalized to sum to 1 over the k picks. The ONE choice rule both
    dispatch paths share — top_k_routing adds capacity assignment on top,
    the dropless path consumes ids/gates directly. ``select`` [N, E]: the
    values the CHOICE is made on where they are not the scores themselves
    (the scores plus a per-expert bias, any sign); the gates are the chosen
    experts' ``probs`` either way. ``eps`` > 0: the gates are divided by
    ``sum + eps`` (a published form) instead of ``max(sum, 1e-9)``."""
    masked = probs if select is None else select
    taken = -1.0 if select is None else -jnp.inf
    ids, gates = [], []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
        gates.append(jnp.sum(probs * onehot, axis=-1))
        # -1 (not *0): if every remaining prob underflowed to exactly 0,
        # multiplicative masking would let argmax re-pick a chosen expert
        # (index 0 of an all-zero row) and burn a capacity slot on it
        masked = jnp.where(onehot > 0, taken, masked)
        ids.append(idx.astype(jnp.int32))
    ids = jnp.stack(ids, axis=1)
    g = jnp.stack(gates, axis=1)
    total = g.sum(axis=1, keepdims=True)
    return ids, g / (total + eps if eps else jnp.maximum(total, 1e-9))


def top_k_routing(probs: Array, k: int, capacity: int):
    """probs [S, E] fp32 -> (dispatch [S, E, C] bool, combine [S, E, C]
    fp32, assign [S, E] fp32) for ONE group.

    Expert CHOICE is ``top_k_choice``. Capacity POSITIONS are assigned
    TOKEN-major: all (token, slot) assignments are flattened in token order
    (t0s0, t0s1, t1s0, ...) before the in-expert cumsum, so a token's
    position — and therefore whether it is dropped — depends only on
    strictly earlier tokens (all their slots) and its own earlier slots.
    That makes the causality guarantee hold for every k, unlike GShard's
    slot-major ordering where a FUTURE token's slot-0 pick can evict an
    earlier token's slot-1 assignment; the price is that slot-0 traffic no
    longer has priority over slot-1 traffic from earlier tokens. Combine
    weights are the chosen experts' probs renormalized to sum to 1 over
    the k choices.
    """
    n, e = probs.shape
    ids, gates_arr = top_k_choice(probs, k)  # [S, k] each, gates normalized
    oh = jax.nn.one_hot(ids, e, dtype=jnp.float32)  # [S, k, E]
    flat = oh.reshape(n * k, e)  # token-major (slot minor) order
    pos = jnp.cumsum(flat, axis=0) - flat  # 0-based in-expert positions
    pos_tok = jnp.sum(pos * flat, axis=-1).reshape(n, k)  # fp32 exact ints
    keep = pos_tok < capacity  # [S, k]
    disp_ke = (oh > 0) & keep[:, :, None]  # [S, k, E]
    slot_oh = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity)  # [S, k, C]
    disp_ksec = disp_ke[..., None] & (slot_oh[:, :, None, :] > 0)  # [S,k,E,C]
    dispatch = disp_ksec.any(axis=1)  # [S, E, C]
    combine = jnp.sum(
        disp_ksec.astype(jnp.float32) * gates_arr[:, :, None, None], axis=1
    )
    assign_frac = oh.sum(axis=1) / k  # [S, E], each row sums to 1
    return dispatch, combine, assign_frac


class MoEMLP(nn.Module):
    """Drop-in replacement for models.transformer.MLP on MoE layers."""

    cfg: ModelConfig
    mesh: Optional[Any] = None
    quant: str = ""  # "" | "int8": weight-streamed decode (orion_tpu/quant.py)

    @nn.compact
    def __call__(self, x: Array, live: Optional[Array] = None) -> Array:
        """``live``: the serving methods' row mask, ``x.shape[:-1]`` bool
        (module docstring); a layer that cannot leave a row out
        (:func:`masks_rows`) computes every row, as it did."""
        cfg = self.cfg
        if live is not None and not masks_rows(cfg, self.quant, self.mesh):
            live = None
        xe, route_on = x, None
        if cfg.moe_latent:
            # the experts work in the latent; the router reads the block's
            # own input (``route_on``), as the shared expert does
            assert cfg.moe_dropless and not self.quant, "the latent is the dropless layer's"
            xe, route_on = self._latent("latent_down", cfg.moe_latent, x), x
        if cfg.moe_held or live is not None:
            y = self._dropless_held(xe, live, route_on)
        else:
            y = self._routed(xe, route_on)
        if cfg.moe_latent:
            y = self._latent("latent_up", x.shape[-1], y)
        if cfg.moe_shared_hidden:
            y = y + self._shared(x)
        return y

    def _latent(self, name: str, features: int, x: Array) -> Array:
        """One of the two projections around the routed experts (``d_model
        -> moe_latent`` before them, back after their weighted sum): no bias,
        norm or activation."""
        cfg = self.cfg
        with scope("moe_latent"):
            return nn.Dense(
                features, use_bias=False, dtype=_dtype(cfg.dtype),
                param_dtype=_dtype(cfg.param_dtype), name=name, **drawn_kernel_init(cfg)
            )(x)

    def _shared(self, x: Array) -> Array:
        """The shared expert every token passes, in the experts' own form
        (:func:`expert_form`: ``shared_gate`` exists where they are gated),
        scaled by sigmoid(w . x) unless ``moe_shared_gated`` is off."""
        cfg = self.cfg
        assert not self.quant, self.quant
        names, act = expert_form(cfg)
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        init = drawn_kernel_init(cfg)
        dense = lambda n, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=dt, param_dtype=pdt, name=n, **init
        )
        with scope("moe_shared"):
            if len(names) == 3:
                mid = act(dense("shared_gate", cfg.moe_shared_hidden)(x)) * dense(
                    "shared_up", cfg.moe_shared_hidden
                )(x)
            else:
                mid = act(dense("shared_up", cfg.moe_shared_hidden)(x))
            if not cfg.moe_shared_gated:
                return dense("shared_down", x.shape[-1])(mid)
            gate = jax.nn.sigmoid(dense("shared_scale", 1)(x).astype(jnp.float32))
            return dense("shared_down", x.shape[-1])(mid) * gate.astype(dt)

    def _routed(self, x: Array, route_on: Optional[Array] = None) -> Array:
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        e, k, h = cfg.n_experts, cfg.moe_top_k, cfg.resolved_moe_hidden
        # k > E would silently re-pick masked experts (argmax over an
        # all -1 row) and leak combine weight — fail loudly instead
        assert 1 <= k <= e, f"moe_top_k={k} must be in [1, n_experts={e}]"
        d = x.shape[-1]
        if cfg.moe_dropless:
            return self._dropless(x, route_on)
        act = expert_form(cfg)[1]
        assert not cfg.moe_route_bias, "the selection bias is the dropless router's"
        single = x.ndim == 2  # decode: [B, D]
        if single:
            xg = x[None]  # one group of B tokens
            s = x.shape[0]
            cap = s  # decode never drops
        else:
            t = x.shape[-2]
            s = _group_size(t, cfg.moe_group_size)
            xg = x.reshape(-1, s, d)  # [G, S, D]: consecutive same-row tokens
            cap = min(s, max(k, math.ceil(cfg.moe_capacity_factor * k * s / e)))
        g = xg.shape[0]

        # -- routing (fp32) --------------------------------------------------
        router = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, param_dtype=pdt, name="router"
        )
        logits = router(xg.astype(jnp.float32))  # [G, S, E]
        probs = jax.nn.softmax(logits, axis=-1)
        dispatch, combine, assign = jax.vmap(
            top_k_routing, in_axes=(0, None, None)
        )(probs, k, cap)

        # aux losses, pre-weighted; no-op unless the caller made "losses"
        # mutable (training does; eval/decode don't). Guarded against init:
        # otherwise model.init would return a junk "losses" collection that
        # pollutes the param tree / TrainState.
        if not self.is_initializing():
            f = assign.mean(axis=(0, 1))  # fraction routed to each expert
            p = probs.mean(axis=(0, 1))  # mean router prob mass per expert
            aux = e * jnp.sum(f * p)
            z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
            self.sow(
                "losses", "moe_aux",
                cfg.moe_aux_weight * aux + cfg.moe_zloss_weight * z,
            )

        # -- expert FFNs (stacked [E, ...], ep-sharded) ----------------------
        # quant mode: int8 stacks + per-(expert, out-channel) scales applied
        # post-einsum (exact for per-out-channel; orion_tpu/quant.py)
        if self.quant:  # expert stacks stay int8 in BOTH quant modes (mixers._dense_factory)
            zi, so = nn.initializers.zeros_init(), nn.initializers.ones_init()

            def qparam(name, shape, out):
                return (
                    self.param(name + "_q", zi, shape, jnp.int8),
                    self.param(name + "_s", so, (e, out), jnp.float32),
                )

            def qein(spec, a, qs, bshape):
                q, s = qs
                y = jnp.einsum(spec, a, q.astype(dt))
                return (y.astype(jnp.float32) * s.reshape(bshape)).astype(dt)

            if cfg.mlp == "swiglu":
                wg = qparam("experts_gate", (e, d, h), h)
                wu = qparam("experts_up", (e, d, h), h)
            else:
                wu = qparam("experts_up", (e, d, h), h)
            wdn = qparam("experts_down", (e, h, d), d)
            xe = jnp.einsum("gsd,gsec->gecd", xg.astype(dt), dispatch.astype(dt))
            xe = self._ep_constraint(xe)
            bs = (1, e, 1, -1)
            if cfg.mlp == "swiglu":
                mid = jax.nn.silu(qein("gecd,edh->gech", xe, wg, bs)) * qein(
                    "gecd,edh->gech", xe, wu, bs
                )
            else:
                mid = act(qein("gecd,edh->gech", xe, wu, bs))
            ye = qein("gech,ehd->gecd", mid, wdn, bs)
            ye = self._ep_constraint(ye)
            y = jnp.einsum("gecd,gsec->gsd", ye, combine.astype(dt))
            return y.reshape(x.shape).astype(dt)

        if cfg.mlp == "swiglu":
            wg = self.param("experts_gate", _expert_init(), (e, d, h), pdt)
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
        else:
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
        wdn = self.param("experts_down", _expert_init(), (e, h, d), pdt)

        xe = jnp.einsum("gsd,gsec->gecd", xg.astype(dt), dispatch.astype(dt))
        xe = self._ep_constraint(xe)
        if cfg.mlp == "swiglu":
            gt = jnp.einsum("gecd,edh->gech", xe, wg.astype(dt))
            up = jnp.einsum("gecd,edh->gech", xe, wu.astype(dt))
            mid = jax.nn.silu(gt) * up
        else:
            mid = act(jnp.einsum("gecd,edh->gech", xe, wu.astype(dt)))
        ye = jnp.einsum("gech,ehd->gecd", mid, wdn.astype(dt))
        ye = self._ep_constraint(ye)
        y = jnp.einsum("gecd,gsec->gsd", ye, combine.astype(dt))
        return y.reshape(x.shape).astype(dt)

    def _route_flat(self, x2: Array):
        """Shared router for the token-flat dropless paths: fp32 logits /
        softmax / top-k choice on [N, d] input. ONE definition so the
        single-host and ep-sharded forms can never diverge."""
        cfg = self.cfg
        init = drawn_kernel_init(cfg)
        router = nn.Dense(
            cfg.resolved_router_width, use_bias=False, dtype=jnp.float32,
            param_dtype=_dtype(cfg.param_dtype), name="router", **init
        )
        logits = router(x2.astype(jnp.float32))  # [N, E]
        if cfg.moe_score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        else:
            assert cfg.moe_score == "sigmoid", cfg.moe_score
            probs = jax.nn.sigmoid(logits)
        select = None
        if cfg.moe_route_bias:
            # a buffer, not a weight: the choice sees it, the gates do not.
            # No gradient reaches it and, one-dimensional a layer, the
            # trainer's decay mask (``_wd_mask``) leaves it alone
            bias = self.param(
                "router_bias", nn.initializers.normal(cfg.moe_route_bias),
                (cfg.resolved_router_width,), jnp.float32,
            )
            select = probs + jax.lax.stop_gradient(bias)
        ids, gates = top_k_choice(  # [N, k] x2
            probs, cfg.moe_top_k, select, cfg.moe_gate_eps
        )
        if cfg.moe_route_scale != 1.0:
            gates = gates * cfg.moe_route_scale
        return logits, probs, ids, gates

    def _sow_flat_aux(self, logits: Array, probs: Array, ids: Array) -> None:
        """Load-balance + z aux losses for the token-flat router (shared by
        both dropless forms); no-op during init."""
        cfg = self.cfg
        if self.is_initializing():
            return
        e = cfg.resolved_router_width
        f = jax.nn.one_hot(ids, e, dtype=jnp.float32).mean(axis=(0, 1))
        p = probs.mean(axis=0)
        aux = e * jnp.sum(f * p)
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        self.sow(
            "losses", "moe_aux",
            cfg.moe_aux_weight * aux + cfg.moe_zloss_weight * z,
        )

    def _dropless(self, x: Array, route_on: Optional[Array] = None) -> Array:
        """Dropless dispatch (SURVEY §7 r2 carry; VERDICT r2 #5): tokens are
        sorted by routed expert and run through ``jax.lax.ragged_dot`` —
        static shapes, exactly the routed FLOPs, and EVERY token reaches
        every chosen expert, so there is no capacity knob and no
        train/serve asymmetry (parallel forward == recurrent decode by
        construction, drops or no). Param names match the capacity path, so
        checkpoints move freely between ``moe_dropless`` settings.

        Causality/batch-independence are trivial here: with no capacity
        contention, a token's output depends only on its own features.

        ep meshes route to ``_dropless_ep`` (static-budget sharded form);
        this body is the single-host (dp/fsdp/tp) path, the one that takes
        ``route_on`` (the router's input where it is not the experts':
        ``moe_latent``).
        """
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        e, k, h = cfg.n_experts, cfg.moe_top_k, cfg.resolved_moe_hidden
        d = x.shape[-1]
        act = expert_form(cfg)[1]
        ep = 1 if self.mesh is None else self.mesh.shape.get("ep", 1)
        assert route_on is None or self.mesh is None or self.mesh.devices.size == 1, (
            "experts in a latent are one device's layer: no exchange is built for it"
        )
        if ep > 1:
            # r3 VERDICT #3: the exact path and the scalable path were
            # disjoint — _dropless_ep removes the single-host assert
            assert not self.quant, (
                "int8 dropless serving is single-host; use ep=1 or the "
                "capacity path on ep meshes"
            )
            from orion_tpu.ops.dispatch import resolve

            b = resolve(cfg.backend)
            n_row_shards = _data_shards(self.mesh)
            n_tok = x.reshape(-1, d).shape[0]
            # gmm form (VERDICT r4 #3a): needs a pallas backend, rows
            # that divide the data axes, and training-scale local row
            # counts (decode's tiny m keeps ragged_dot)
            if (
                b.startswith("pallas")
                and n_tok % n_row_shards == 0
                and (n_tok // n_row_shards) * cfg.moe_top_k >= 1024
            ):
                return self._dropless_ep_gmm(
                    x, interpret=(b == "pallas_interpret")
                )
            return self._dropless_ep(x)
        if self.mesh is not None and self.mesh.devices.size > 1:
            # GSPMD dense meshes (ep == 1, dp/fsdp/sp data axes): the
            # ragged GSPMD form below shards cleanly but pays the
            # ragged_dot price. The manual gmm region handles ep == 1 as
            # a degenerate case — per-data-shard counting sort + gmm,
            # budget pinned to m_loc so it stays EXACT dropless (VERDICT
            # r4 #3b "gmm under GSPMD meshes"). tp > 1 keeps ragged: the
            # manual region would gather the tp-sharded expert stacks
            # whole and duplicate their FLOPs per tp shard, which loses
            # more than the kernel wins.
            from orion_tpu.ops.dispatch import resolve

            b = resolve(cfg.backend)
            s = self.mesh.shape
            n_row_shards = _data_shards(self.mesh)
            n_tok = x.reshape(-1, d).shape[0]
            if (
                b.startswith("pallas")
                and not self.quant
                and "ep" in self.mesh.axis_names
                and s.get("tp", 1) == 1
                # pp == 1: pipelined models reach MoE through
                # pipeline_lm.py, which builds blocks with mesh=None (the
                # single-host path below serves them inside the manual
                # region); a DIRECT apply on a pp mesh would replicate the
                # row work per pp shard here, so keep it on ragged GSPMD
                and s.get("pp", 1) == 1
                and n_tok % n_row_shards == 0
                and (n_tok // n_row_shards) * cfg.moe_top_k >= 1024
            ):
                return self._dropless_ep_gmm(
                    x, interpret=(b == "pallas_interpret")
                )
        x2 = x.reshape(-1, d)
        n = x2.shape[0]

        logits, probs, ids, gates = self._route_flat(
            x2 if route_on is None else route_on.reshape(n, -1)
        )
        self._sow_flat_aux(logits, probs, ids)

        flat = ids.reshape(-1)  # [N*k], token-major
        from orion_tpu.ops.dispatch import resolve

        b = resolve(cfg.backend)
        # grouped-matmul Mosaic kernel (ops/pallas/gmm.py): tile-aligned
        # expert segments instead of ragged groups. Worth it at training
        # row counts; decode calls (tiny m) and the quant path (per-row
        # scale tables) keep ragged_dot. Single-device meshes only: GSPMD
        # cannot auto-partition a Mosaic call (parallel/kernel_shard.py);
        # multi-device meshes were routed above (tp == 1 dense meshes into
        # the manual gmm region, ep meshes into _dropless_ep*) and what
        # reaches this gate sharded (tp > 1, misaligned rows, tiny m)
        # keeps the ragged form, whose token-local ops shard cleanly.
        if (
            b.startswith("pallas")
            and flat.shape[0] >= 1024
            and not self.quant
            and (self.mesh is None or self.mesh.devices.size == 1)
        ):
            return self._dropless_gmm(
                x, x2, flat, gates, interpret=(b == "pallas_interpret")
            )
        order, inv, counts = _counting_sort_perm(flat, e)
        xs = jnp.take(x2.astype(dt), order // k, axis=0)  # [N*k, d]
        sorted_ids = jnp.take(flat, order, axis=0)  # for quant scale rows

        if self.quant:  # expert stacks stay int8 in BOTH quant modes (mixers._dense_factory)
            zi, so = nn.initializers.zeros_init(), nn.initializers.ones_init()

            def qrd(name, shape, out, lhs):
                q = self.param(name + "_q", zi, shape, jnp.int8)
                s = self.param(name + "_s", so, (e, out), jnp.float32)
                y = jax.lax.ragged_dot(lhs, q.astype(dt), counts)
                srow = jnp.take(s, sorted_ids, axis=0)  # [N*k, out]
                return (y.astype(jnp.float32) * srow).astype(dt)

            if cfg.mlp == "swiglu":
                mid = jax.nn.silu(qrd("experts_gate", (e, d, h), h, xs)) * qrd(
                    "experts_up", (e, d, h), h, xs
                )
            else:
                mid = act(qrd("experts_up", (e, d, h), h, xs))
            ys = qrd("experts_down", (e, h, d), d, mid)
        else:
            if cfg.mlp == "swiglu":
                wg = self.param("experts_gate", _expert_init(), (e, d, h), pdt)
                wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
            else:
                wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
            wdn = self.param("experts_down", _expert_init(), (e, h, d), pdt)

            def rd(lhs, w):
                return jax.lax.ragged_dot(lhs, w.astype(dt), counts)

            if cfg.mlp == "swiglu":
                mid = jax.nn.silu(rd(xs, wg)) * rd(xs, wu)
            else:
                mid = act(rd(xs, wu))
            ys = rd(mid, wdn)

        y = jnp.take(ys, inv, axis=0).reshape(n, k, d)
        y = jnp.sum(y * gates[..., None].astype(dt), axis=1)
        return y.reshape(x.shape).astype(dt)

    def _dropless_held(
        self, x: Array, live: Optional[Array] = None, route_on: Optional[Array] = None
    ) -> Array:
        """The dropless layer of ONE chip of an expert-parallel group: the
        router is ``moe_router_width`` wide and picks its top-k over all of
        it, renormalised over all k as published; of the chosen (token,
        expert) rows only those whose expert is held here — ids
        ``[moe_expert_offset, moe_expert_offset + n_experts)`` — are
        computed (``_held_rows_ffn``, the body an ep shard runs too), and
        the others add nothing (their chips add them). Single-device only:
        on one chip the layer runs without its exchange. A chip that holds
        every expert of its router (served with ``live``: ``__call__``) is
        the case with nothing left out.

        The buffer is ``moe_ep_buffer x`` the held experts' even share of
        the rows (an even router fills ``1 / moe_ep_buffer`` of it; one of
        ``router_width / n_experts`` times the share holds every row there
        can be); rows past it are dropped and COUNTED. ``live`` (the serving
        methods' row mask): the pairs of a row outside it belong to no
        expert and to no counter, and the grouped product visits only the
        tiles that hold a row. The experts take either form (:func:`expert_form`:
        three grouped products a layer or two). ``route_on``: the router's
        input where it is not the experts' (``moe_latent``: ``x`` is then the
        latent, and everything buffer-sized here is that wide)."""
        cfg = self.cfg
        assert cfg.moe_dropless and not self.quant
        assert self.mesh is None or self.mesh.devices.size == 1, (
            "the held-experts layer is one chip's share; it has no exchange"
        )
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        e, k, h = cfg.n_experts, cfg.moe_top_k, cfg.resolved_moe_hidden
        r, lo = cfg.resolved_router_width, cfg.moe_expert_offset
        assert 1 <= k <= r and 0 <= lo and lo + e <= r, (k, r, lo, e)
        d = x.shape[-1]
        x2 = x.reshape(-1, d)
        m = x2.shape[0] * k
        budget = min(m, -(-int(math.ceil(cfg.moe_ep_buffer * m * e / r)) // 8) * 8)

        from orion_tpu.ops.dispatch import resolve

        b = resolve(cfg.backend)
        with scope("moe_route"):
            logits, probs, ids, gates = self._route_flat(
                x2 if route_on is None else route_on.reshape(x2.shape[0], -1)
            )
            self._sow_flat_aux(logits, probs, ids)
        names, act = expert_form(cfg)
        ws = tuple(
            self.param(
                f"experts_{name}", drawn_in(cfg, _expert_init()),
                (e, h, d) if name == "down" else (e, d, h), pdt,
            )
            for name in names
        )
        flat, routed = ids.reshape(-1), jnp.asarray(m, jnp.int32)
        tm, bh = _TILES
        if live is not None:
            alive = jnp.repeat(live.reshape(-1), k)
            flat = jnp.where(alive, flat, -1)  # no expert's id: held nowhere
            routed = alive.sum().astype(jnp.int32)
            tm, bh = serve_tiles(m, r, cfg.moe_step_tile, d, h, jnp.dtype(dt).itemsize)
        if b.startswith("pallas") and (live is not None or budget >= 1024):
            matmul = _gmm_matmul(tm, bh, b == "pallas_interpret", served=live is not None)
        else:
            matmul = _ragged_matmul
        y, held_counts, dropped = _held_rows_ffn(
            x2, flat, gates.reshape(-1), ws, lo, budget, matmul, dt, act
        )
        if not self.is_initializing():
            self.sow("moe_stats", "dropless_overflow", dropped)
            self.sow("moe_stats", "rows_routed", routed)
            self.sow("moe_stats", "rows_held", held_counts.sum())
            self.sow("moe_stats", "rows_max_expert", held_counts.max())
            if live is not None:
                # the served grouped product's visits, on whichever backend:
                # tiles / experts is how often the blocked order streams an
                # expert, 1 - experts / tiles the visits a resident block serves
                self.sow("moe_stats", "tiles_live", (-(-held_counts // tm)).sum())
                self.sow("moe_stats", "experts_live", (held_counts > 0).sum())
            else:
                # the training product's visits (its tile on either backend):
                # over layers x the buffer's tiles, the share of its grid that
                # holds a row. Rows past the budget have no tile
                kept = jnp.diff(jnp.minimum(jnp.cumsum(held_counts), budget), prepend=0)
                self.sow("moe_stats", "tiles_live", (-(-kept // tm)).sum())
        return y.reshape(x.shape).astype(dt)

    def _dropless_gmm(
        self, x: Array, x2: Array, flat: Array, gates: Array, interpret: bool
    ) -> Array:
        """Dropless expert FFNs through the grouped-matmul kernel
        (ops/pallas/gmm.py). Rows are scattered into TILE-ALIGNED expert
        segments (pad rows are zeros — they flow through the FFN as zeros
        and contribute nothing to dw), so the kernel runs dense MXU tiles
        with a scalar-prefetched tile->expert table. <= E*(tile-1) wasted
        rows, ~2% at flagship shapes."""
        from orion_tpu.ops.pallas.gmm import gmm, pad_group_sizes

        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        e, k, h = cfg.n_experts, cfg.moe_top_k, cfg.resolved_moe_hidden
        d = x2.shape[-1]
        m = flat.shape[0]
        # (128, 512) is the VMEM-feasible optimum at flagship shapes: the
        # r4 on-chip sweep measured tm=256 and bh=1024 variants OOMing the
        # 16MB VMEM stack on the wide-d (5504) matmuls' blocks
        tm, bh = 128, 512
        _, rank, counts = _counting_sort_perm(flat, e)
        offs_tight = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
        )
        seg, starts = pad_group_sizes(counts, tm)
        pos = starts[flat] + (rank - offs_tight[flat])  # padded row slot
        m2 = -(-(m + e * tm) // tm) * tm
        xs = jnp.zeros((m2, d), dt).at[pos].set(
            jnp.take(x2.astype(dt), jnp.arange(m) // k, axis=0)
        )

        if cfg.mlp == "swiglu":
            wg = self.param("experts_gate", _expert_init(), (e, d, h), pdt)
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
            mid = jax.nn.silu(gmm(xs, wg, seg, tm, bh, interpret)) * gmm(
                xs, wu, seg, tm, bh, interpret
            )
        else:
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
            mid = expert_form(cfg)[1](gmm(xs, wu, seg, tm, bh, interpret))
        wdn = self.param("experts_down", _expert_init(), (e, h, d), pdt)
        ys = gmm(mid, wdn, seg, tm, bh, interpret)  # [M2, d]

        n = m // k
        y = jnp.take(ys, pos, axis=0).reshape(n, k, d)
        y = jnp.sum(y * gates[..., None].astype(dt), axis=1)
        return y.reshape(x.shape).astype(dt)

    def _dropless_ep(self, x: Array) -> Array:
        """Dropless dispatch sharded over the ep axis (r3 VERDICT #3b).

        Tokens are replicated over ep (batch rides dp/fsdp), so no token
        exchange is needed at all — each shard serves its E/ep local
        experts and the outputs meet in one psum:

          1. route (replicated fp32 math, identical on every shard);
          2. per shard: counting-sort rows by ROTATED expert id
             ((expert - shard_lo) mod E) so this shard's experts form the
             sorted prefix; take the first B rows (B static);
          3. ragged_dot against the local expert stack AUGMENTED with one
             zero expert that absorbs the remote rows inside the budget —
             they contribute exactly 0 and their owners compute them;
          4. scatter back to row positions, psum over ep.

        B = moe_ep_buffer·M/ep (configs.py): >= ep is mathematically
        dropless; below that, rows past a shard's budget are dropped and
        COUNTED (sown into "moe_stats"/"dropless_overflow"), never silent.
        The capacity path remains the bounded-activation alternative.
        """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        e, k, h = cfg.n_experts, cfg.moe_top_k, cfg.resolved_moe_hidden
        d = x.shape[-1]
        ep = self.mesh.shape["ep"]
        assert e % ep == 0, (e, ep)
        el = e // ep
        x2 = x.reshape(-1, d)
        n = x2.shape[0]
        m = n * k
        budget = int(math.ceil(cfg.moe_ep_buffer * m / ep))
        budget = min(m, max(el, (budget + 7) // 8 * 8))

        logits, probs, ids, gates = self._route_flat(x2)

        if cfg.mlp == "swiglu":
            wg = self.param("experts_gate", _expert_init(), (e, d, h), pdt)
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
        else:
            wg = None
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
        wdn = self.param("experts_down", _expert_init(), (e, h, d), pdt)

        def body(xl, flat, *ws):
            r = jax.lax.axis_index("ep")
            lo = r * el
            rot = (flat - lo) % e  # local experts become classes 0..el-1
            order, _, counts_rot = _counting_sort_perm(rot, e)
            sel = order[:budget]  # local-expert rows first, expert-major
            xs = jnp.take(xl.astype(dt), sel // k, axis=0)  # [B, d]
            cum = jnp.cumsum(counts_rot[:el])
            cumc = jnp.minimum(cum, budget)
            gs_local = jnp.diff(cumc, prepend=0)
            gs = jnp.concatenate(
                [gs_local, (budget - cumc[-1])[None]]
            ).astype(jnp.int32)

            def aug(w):
                # one zero expert absorbs the in-budget remote rows
                return jnp.concatenate(
                    [w.astype(dt), jnp.zeros((1,) + w.shape[1:], dt)], axis=0
                )

            if cfg.mlp == "swiglu":
                wgl, wul, wdl = ws
                mid = jax.nn.silu(
                    jax.lax.ragged_dot(xs, aug(wgl), gs)
                ) * jax.lax.ragged_dot(xs, aug(wul), gs)
            else:
                wul, wdl = ws
                mid = expert_form(cfg)[1](jax.lax.ragged_dot(xs, aug(wul), gs))
            ys = jax.lax.ragged_dot(mid, aug(wdl), gs)  # [B, d]
            part = jnp.zeros((m, d), dt).at[sel].set(ys)
            part = jax.lax.psum(part, "ep")
            dropped = jax.lax.psum(cum[-1] - cumc[-1], "ep")
            return part, dropped

        ws = tuple(w for w in (wg, wu, wdn) if w is not None)
        wspec = P("ep", None, None)
        fn = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(None, None), P(None)) + (wspec,) * len(ws),
            out_specs=(P(None, None), P()),
            axis_names=frozenset({"ep"}),
        )
        part, dropped = fn(x2, ids.reshape(-1), *ws)

        self._sow_flat_aux(logits, probs, ids)
        if not self.is_initializing():
            # overflow is a diagnostic, not a loss term: rows past a
            # shard's budget (only possible when moe_ep_buffer < ep and
            # the router is extremely imbalanced) are dropped and counted
            self.sow("moe_stats", "dropless_overflow", dropped)

        y = part.reshape(n, k, d)
        y = jnp.sum(y * gates[..., None].astype(dt), axis=1)
        return y.reshape(x.shape).astype(dt)

    def _dropless_ep_gmm(self, x: Array, interpret: bool) -> Array:
        """Dropless-ep with the grouped-matmul kernel INSIDE the ep region
        (VERDICT r4 #3a: the scalable dropless form paid the ragged_dot
        price the kernel was built to remove). Also the GSPMD dense-mesh
        entry (VERDICT r4 #3b): with ep == 1 every expert is shard-local,
        the budget pins to ``m_loc`` (exact dropless, zero overflow by
        construction), and the body degenerates to a per-data-shard
        counting sort + gmm with no cross-shard token exchange at all —
        the kernel_shard-style manualization the r4 carry named, with the
        sorting done per shard.

        Differences from the ragged ``_dropless_ep``:

        - the shard_map is FULLY manual (every mesh axis named): jax's
          tpu_custom_call lowering rejects Mosaic calls in partial-manual
          regions (parallel/kernel_shard.py), so going fully manual is
          what makes the kernel legal here at all;
        - token rows are SHARDED over (dp, fsdp, sp) instead of
          replicated — each shard sorts and serves only its local rows
          (the ragged form recomputed every token on every ep shard);
          the static budget applies per (data-shard, ep-shard):
          ``ceil(moe_ep_buffer * m_local / ep)``, the same proportion of
          local traffic the global budget gave;
        - local rows sit in TILE-ALIGNED per-expert segments (the gmm
          contract) instead of a sorted prefix, and each token sums its
          local experts' rows, gate-weighted, BEFORE the psum
          (``_held_rows_ffn``, the body ``_dropless_held`` runs too):
          the exchange carries [n_loc, d], not [m_loc, d]; remote and
          over-budget rows never enter the buffer — no zero-expert
          augmentation needed;
        - expert weights are pcast data-axis-varying inside the body so
          the shard_map transpose psums dw over the data axes (the same
          idiom as ops/fused_ce.py::_sp_fused_ce).

        Parity vs the ragged form and vs the single-host path:
        tests/test_moe.py (interpret mode); the real-Mosaic compile is
        covered by the fsdp x ep topology-AOT artifact and the driver
        dryrun line."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        e, k, h = cfg.n_experts, cfg.moe_top_k, cfg.resolved_moe_hidden
        d = x.shape[-1]
        mesh = self.mesh
        s = mesh.shape
        ep = s["ep"]
        assert e % ep == 0, (e, ep)
        el = e // ep
        row_axes = _data_axes(mesh)
        n_rows_shards = _data_shards(mesh)
        x2 = x.reshape(-1, d)
        n = x2.shape[0]
        assert n % n_rows_shards == 0, (n, dict(s))
        m_loc = (n // n_rows_shards) * k
        if ep == 1:
            # GSPMD dense-mesh entry (ep == 1): every expert is local, so
            # a full budget makes the form EXACT dropless — matching the
            # single-host path's semantics (no budget knob there either)
            budget = m_loc
        else:
            budget = int(math.ceil(cfg.moe_ep_buffer * m_loc / ep))
            budget = min(m_loc, max(el, (budget + 7) // 8 * 8))
        matmul = _gmm_matmul(*((8, 128) if interpret else (128, 512)), interpret)

        logits, probs, ids, gates = self._route_flat(x2)

        if cfg.mlp == "swiglu":
            wg = self.param("experts_gate", _expert_init(), (e, d, h), pdt)
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
        else:
            wg = None
            wu = self.param("experts_up", _expert_init(), (e, d, h), pdt)
        wdn = self.param("experts_down", _expert_init(), (e, h, d), pdt)

        def body(xl, flat, gl, *ws):
            lo = jax.lax.axis_index("ep") * el
            if row_axes and not interpret:
                # dw transpose -> psum over the data axes (the fused_ce
                # idiom). Interpret mode runs check_vma=False, where the
                # cast's transpose psum trips the variant check — the
                # legacy spec-based transpose handles the replicated
                # input there instead.
                ws = tuple(
                    jax.lax.pcast(w, row_axes, to="varying") for w in ws
                )
            y, _, dropped = _held_rows_ffn(
                xl, flat, gl, ws, lo, budget, matmul, dt, expert_form(cfg)[1]
            )
            return (
                jax.lax.psum(y, "ep"),  # [n_loc, d]
                jax.lax.psum(dropped, ("ep",) + row_axes),
            )

        ws = tuple(w for w in (wg, wu, wdn) if w is not None)
        rs = row_axes if row_axes else None
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(rs, None), P(rs), P(rs))
            + (P("ep", None, None),) * len(ws),
            out_specs=(P(rs, None), P()),
            axis_names=frozenset(mesh.axis_names),  # fully manual (Mosaic)
            # vma on for real Mosaic (REQUIRED — tpu_custom_call rejects
            # unchecked regions, parallel/kernel_shard.py); interpret-mode
            # tracing cannot run under the check (same constraint as
            # sequence.py/ring.py)
            check_vma=not interpret,
        )
        y, dropped = fn(x2, ids.reshape(-1), gates.reshape(-1), *ws)

        self._sow_flat_aux(logits, probs, ids)
        if not self.is_initializing():
            self.sow("moe_stats", "dropless_overflow", dropped)
        return y.reshape(x.shape).astype(dt)

    def _ep_constraint(self, t: Array) -> Array:
        """Pin the expert-major activation layout to the ep axis so GSPMD
        emits one all_to_all-class exchange instead of replicating
        [G,E,C,D]."""
        if self.mesh is not None and self.mesh.shape.get("ep", 1) > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            ep = self.mesh.shape["ep"]
            # E % ep != 0 would silently replicate the full [G,E,C,D]
            # dispatch tensor on every device — an OOM-by-surprise at pod
            # scale. Fail loudly like the k<=E assert above.
            assert t.shape[1] % ep == 0, (
                f"n_experts={t.shape[1]} must divide evenly over mesh "
                f"ep={ep}; otherwise the dispatch tensor replicates"
            )
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, P(None, "ep", None, None))
            )
        return t


def _data_axes(mesh) -> tuple:
    """Token-row mesh axes (only those the mesh actually has — raw
    ep-only test meshes exist). ONE definition shared by the gmm gate and
    _dropless_ep_gmm so the two can never drift (r5 review)."""
    return tuple(a for a in ("dp", "fsdp", "sp") if a in mesh.axis_names)


def _data_shards(mesh) -> int:
    s = mesh.shape
    out = 1
    for a in _data_axes(mesh):
        out *= s.get(a, 1)
    return out


# the held layer's row tile and output block of the grouped product: in
# training, and in serving but for what :func:`serve_tiles` decides
_TILES = (128, 512)


def serve_tiles(m: int, r: int, step_tile: int, d: int, h: int, itemsize: int):
    """(row tile, output block) of the served grouped product, from a call's
    shapes alone: ``m`` (token, expert) pairs over a router ``r`` wide, the
    configuration's step tile, experts ``[d, h]`` of ``itemsize`` bytes. An
    even router gives an expert ``m / r`` rows. A step (an expert's rows are
    few) takes the small tile. A call that can give an expert a tile or more
    (four 1,024-row pieces to a program: 256 rows) gives it several under a
    real router, so its block is the WHOLE width, ``None``: ``gmm_live`` then
    holds an expert's matrix across its consecutive tiles and streams it
    once, where blocks of 512 stream it once a tile; taken only where two
    buffers of the matrix fit the kernel's VMEM budget
    (``gmm.live_whole_width_fits``). Every other call keeps the block of 512:
    with one tile an expert the blocked order already reads the weights once,
    and a whole-width block there only exposes its first fetch."""
    from orion_tpu.ops.pallas.gmm import live_whole_width_fits

    tm, bh = _TILES
    if m <= step_tile * r:  # a step: an expert's rows are few
        tm = step_tile
    if m // r >= tm and live_whole_width_fits(d, h, itemsize):
        bh = None
    return tm, bh


def _gmm_matmul(tm: int, bh: Optional[int], interpret: bool, served: bool = False):
    """``_held_rows_ffn``'s matmul through the grouped-matmul kernel, which
    wants every expert's rows in whole tiles of ``tm``. Either form visits the
    tiles up to the last segment's end and leaves the rows past it unwritten
    (``matmul.unwritten_tail``: the caller never reads them unmasked).
    ``served``: the forward-only ``gmm_live``, whose ``bh`` may be ``None``,
    each product's whole width held resident (:func:`serve_tiles`), and whose
    rows move by ``jnp.take``; training's ``gmm`` has a backward and its rows
    move by list (``matmul.by_list``)."""
    from orion_tpu.ops.pallas.gmm import gmm, gmm_live

    def matmul(lhs, w, seg, gs):
        if served:
            return gmm_live(lhs, w, seg.astype(jnp.int32), tm, bh, interpret)
        return gmm(lhs, w, seg.astype(jnp.int32), tm, bh, interpret)

    matmul.tile = tm
    matmul.unwritten_tail = True
    matmul.by_list = not served
    matmul.interpret = interpret
    return matmul


def _ragged_matmul(lhs, w, seg, gs):
    """``_held_rows_ffn``'s matmul as ``ragged_dot``: tight segments, and one
    zero expert that absorbs the spare rows of the buffer."""
    w = jnp.concatenate([w, jnp.zeros((1,) + w.shape[1:], w.dtype)], axis=0)
    gs = jnp.concatenate([gs, (lhs.shape[0] - gs.sum())[None]]).astype(jnp.int32)
    return jax.lax.ragged_dot(lhs, w, gs)


_ragged_matmul.tile = 1
_ragged_matmul.unwritten_tail = False
_ragged_matmul.by_list = False


def _held_rows_ffn(x2, flat, gates, ws, lo, budget: int, matmul, dt, act=jax.nn.silu):
    """What the experts held here add to each token: the ONE sort-and-matmul
    body of the expert-parallel forms (an ep shard of ``_dropless_ep_gmm``,
    where ``lo`` is the shard's first expert, and ``_dropless_held``, one
    chip's share with ``lo`` fixed and no exchange).

    ``x2 [N, d]``; ``flat``, ``gates`` ``[M = N k]`` token-major (pair ``p``
    is slot ``p % k`` of token ``p // k``); ``ws`` the held experts' stacks
    ``[El, ...]`` (gate, up, down or up, down) and ``act`` their activation
    (:func:`expert_form`; the default is the gated three's), experts ``[lo,
    lo + El)``.
    Returns (``y [N, d]`` fp32, rows per held expert ``[El]``, rows dropped).

    Held rows are counting-sorted by local expert (every other expert is one
    class more, sorted last) into a buffer of static size: ``budget`` rows
    plus, for a tiled matmul, a tile's padding an expert. Rows past the
    budget — the busiest tail of the last experts — are dropped and
    counted. Everything M-sized is an index: the buffer's row ``r`` belongs
    to local expert ``c`` at offset ``off`` of its segment, so it is pair
    ``order[tight[c] + off]``, and only buffer-sized arrays are d wide (at a
    router 8 x the held experts, [M, d] is 8 x the rows that do work).

    Three forms, told apart by what ``matmul`` says of itself: the tiled
    Mosaic product in training moves the rows into the buffer and out of it
    by list in Mosaic kernels (``by_list``: ``ops/pallas/moe_rows.py``, with
    their own backward); serving takes them with ``jnp.take`` and gathers its
    combine (``_gather_combine``); ``_ragged_matmul`` is the plain form,
    ``jnp.take`` and a scatter-add, which the tests hold the other two to.
    Both tiled products leave the rows past the last segment UNWRITTEN
    (``unwritten_tail``), in training in every cotangent of the buffer too:
    everything between the products works a row at a time, and the buffer is
    read by list (the row kernels, the combine's gather), so what those rows
    hold reaches nothing; the plain form's scatter-add reads every row under
    a gate of 0 and needs them finite, which ``ragged_dot`` gives."""
    el, tm = ws[0].shape[0], matmul.tile
    (n, d), m = x2.shape, flat.shape[0]
    k = m // n
    m2 = -(-(budget + (el * tm if tm > 1 else 0)) // tm) * tm
    with scope("moe_route"):
        loc = flat - lo
        cls = jnp.where((loc >= 0) & (loc < el), loc, el)  # el: held elsewhere
        order, rank, counts = _counting_sort_perm(cls, el + 1)
        held = counts[:el]
        cum = jnp.cumsum(held)
        cumc = jnp.minimum(cum, budget)
        gs = jnp.diff(cumc, prepend=0)  # in-budget rows per held expert
        tight = cum - held  # held classes sort first: their starts in order
        seg = -(-gs // tm) * tm
        starts = jnp.cumsum(seg) - seg
        row = jnp.arange(m2, dtype=jnp.int32)
        c = jnp.sum(row[:, None] >= starts[None, :], axis=1) - 1
        off = row - starts[c]
        valid = off < gs[c]  # else tile padding / spare buffer
        pair = order[jnp.clip(tight[c] + off, 0, m - 1)]
        token = pair // k
        by_list = matmul.by_list
        if by_list:
            from orion_tpu.ops.pallas import moe_rows

            # the same rows from the pairs' side: pair p of class c sits at
            # offset rank[p] - tight[c] of c's segment (a select over [M, el]
            # reads the per-expert tables: XLA's gather costs a pair ~15 ns)
            mine = cls[:, None] == jnp.arange(el, dtype=cls.dtype)[None, :]
            per_pair = lambda table: jnp.sum(jnp.where(mine, table[None, :], 0), axis=1)  # noqa: E731
            listed = jnp.where(valid, token, -1)
            lists = moe_rows.combine_lists(
                rank < per_pair(tight + gs), rank + per_pair(starts - tight),
                jax.lax.stop_gradient(gates), n,
            )
            # everything the experts and the backward read of the sort, by
            # name: a rematted block that lists it (models/transformer.py::
            # REMAT_KEEPS) holds these few MB and leaves the counting sort,
            # the [M, el] selects and the lists' [slots, k, N] sums out of
            # its recompute; the router's floats are computed again
            listed, lists, seg, gs, valid, pair = jax.tree.map(
                lambda a: checkpoint_name(a, "moe_lists"),
                (listed, lists, seg, gs, valid, pair),
            )
        gate_row = jnp.where(valid, gates[pair], 0.0)

    with scope("moe_experts"):
        # pad rows are zeros: they flow through the FFN as zeros
        if by_list:
            xs = moe_rows.gather_rows(x2.astype(dt), listed, lists, tm, matmul.interpret)
        else:
            xs = jnp.where(valid[:, None], jnp.take(x2.astype(dt), token, axis=0), 0)
        mm = lambda lhs, w: matmul(lhs, w.astype(dt), seg, gs)  # noqa: E731
        if len(ws) == 3:
            mid = act(mm(xs, ws[0])) * mm(xs, ws[1])
        else:
            mid = act(mm(xs, ws[0]))
        ys = mm(mid, ws[-1])  # [M2, d]
        if matmul.unwritten_tail and not by_list:
            return _gather_combine(
                ys, cls, rank, gates, tight, gs, starts, n
            ), held, cum[-1] - cumc[-1]
        # each token gathers its held experts' rows, weighted
        if by_list:
            y = moe_rows.combine_rows(ys, gate_row, listed, lists, tm, matmul.interpret)
        else:
            y = jnp.zeros((n, d), jnp.float32).at[token].add(
                ys.astype(jnp.float32) * gate_row[:, None]
            )
    return y, held, cum[-1] - cumc[-1]


def _gather_combine(ys, cls, rank, gates, tight, gs, starts, n: int):
    """``_held_rows_ffn``'s combine on the serving path, from the pairs' side:
    pair ``p`` of class ``c`` sits at offset ``rank[p] - tight[c]`` of its
    expert's segment, so each token GATHERS the buffer rows of its k pairs
    and sums them under their gates (a pair held elsewhere or past the budget
    adds nothing, and never reads a row the grouped product left unwritten).
    A scatter-add of the buffer's rows into the tokens costs the chip a
    serial update a row: 3 ms a layer at a 1,024-token piece's 10,240-row
    buffer against 0.5 ms for this gather (PERF.md section 6, PR 43)."""
    el = gs.shape[0]
    k = cls.shape[0] // n
    mine = jnp.minimum(cls, el - 1)
    off = rank - tight[mine]
    ok = (cls < el) & (off < gs[mine])
    rows = jnp.where(ok, starts[mine] + off, 0)
    picked = jnp.where(ok[:, None], jnp.take(ys, rows, axis=0), 0).astype(jnp.float32)
    weighted = picked * jnp.where(ok, gates, 0.0)[:, None]
    return weighted.reshape(n, k, -1).sum(axis=1)


def _counting_sort_perm(flat: Array, n_classes: int):
    """Stable grouping permutation of ``flat`` ([M] int32 class ids) by
    counting sort: (order [M], inv [M], counts [n_classes]) such that
    ``flat[order]`` is sorted (stable) and ``inv`` is order's inverse.

    Equivalent to two ``jnp.argsort``s but O(M·E) elementwise + one
    scatter instead of two O(M log^2 M) bitonic sorts — at the 1.3B MoE
    operating point (M = 24k rows, E = 4) the argsorts were the measured
    hot spot of the dropless layer (BASELINE.md r3 "dropless costs 14.3%";
    r4 re-measure after this change)."""
    m = flat.shape[0]
    oh = (flat[:, None] == jnp.arange(n_classes, dtype=flat.dtype)[None, :])
    ohi = oh.astype(jnp.int32)
    counts = ohi.sum(axis=0)  # [E]
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    within = jnp.cumsum(ohi, axis=0) - ohi  # rank within own class
    rank = jnp.sum((within + offs[None, :]) * ohi, axis=1)  # [M] = inv
    order = jnp.zeros((m,), jnp.int32).at[rank].set(
        jnp.arange(m, dtype=jnp.int32)
    )
    return order, rank, counts


def _group_size(t: int, target: int) -> int:
    """Largest divisor of ``t`` not exceeding ``target`` (so groups tile the
    sequence exactly and never span rows).

    Warns when the resolved size collapses far below ``target`` (e.g. prime
    T forces groups of 1): with one token per group, per-expert capacity can
    never bind, so training-time token dropping silently disappears and the
    routing regime diverges from the documented capacity-factor semantics.
    """
    if target <= 0 or t <= target:
        return t
    for s in range(min(target, t), 0, -1):
        if t % s == 0:
            if s * 4 <= min(target, t):
                import warnings

                warnings.warn(
                    f"moe group size degenerated to {s} (target {target}, "
                    f"seq len {t} has no larger divisor <= target); capacity"
                    f"-based dropping is ineffective at tiny group sizes — "
                    f"pick a seq len with a divisor near moe_group_size",
                    stacklevel=3,
                )
            return s
    return t


# what a held layer sows into "moe_stats", in :func:`stats_vector`'s order
STAT_NAMES = (
    "rows_routed", "rows_held", "rows_max_expert", "dropless_overflow",
    # a SERVED layer's alone (``live``): the row tiles its grouped product
    # visits and the experts that got a row
    "tiles_live", "experts_live",
)


def stats_vector(collection) -> Array:
    """The "moe_stats" collection of one ``apply`` as ``[6]`` int32 in
    :data:`STAT_NAMES`' order, each summed over the layers that sowed it."""
    total = {name: jnp.zeros((), jnp.int32) for name in STAT_NAMES}
    for path, leaf in jax.tree_util.tree_leaves_with_path(collection):
        sown = next(
            p.key for p in reversed(path) if isinstance(p, jax.tree_util.DictKey)
        )
        total[sown] = total[sown] + leaf.astype(jnp.int32)
    return jnp.stack([total[name] for name in STAT_NAMES])


__all__ = [
    "MoEMLP", "STAT_NAMES", "expert_form", "masks_rows", "serve_tiles", "stats_vector", "top_k_routing",
    "top_k_choice",
]
