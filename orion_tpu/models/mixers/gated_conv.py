"""``gated_conv``: a gated short convolution ALONE as the token mixer (the
LFM2 family's ``conv`` layers). With ``z`` the normed input, ``C =
cfg.d_model`` channels and ``W = WIDTH`` = 3 taps (the family's
``conv_L_cache``; a constant until a second width is served):

    [b | c | u] = W_in z                    widths C | C | C, in that order
    v_t = b_t * u_t
    s_t = sum_j w_j v_{t-(W-1)+j}           causal, depthwise, w_{W-1} on the
                                            current token; NO bias, NO activation
    out = W_out (c_t * s_t)

There is no state matrix and no cache: served, the decode state is ``{"conv":
[B, (W - 1) x C]}``, the last ``W - 1`` rows of ``v``, oldest first, side by
side (as ``ssm.py`` and ``gated_delta.py`` hold their conv's), whatever the
prompt's length (``tail_leaves``: what the engine's memory account names
``tail_bytes``). The prompt and its pieces go through
``ops.dispatch.causal_short_conv(activation=False, tail=...)`` (under a
Pallas backend the Mosaic kernel pair of ``ops/pallas/short_conv.py``); a
padded piece takes its new tail at its real ``length``. The one-token step
sums its taps inline and, given a row list, selects back the tail of an
unlisted row (``rows_in_place``: the decode programs then select nothing).
The training forward is the same conv without a tail: autodiff of it (and of
``short_conv_bwd``) is the gradient. Speculative decode is not built for this
mixer: the base class's raise.

Scopes: ``gated_conv`` around every entry point, ``gated_conv_in`` (the
projection and ``b * u``), ``gated_conv_conv`` (the conv; NOT ``short_conv``,
which names the delta-rule and state-space layers' SiLU'd one) and
``gated_conv_out`` (``c * s`` and ``W_out``) inside it.

The plain reference it is tested against is ``benchmark/reference/
plain_lfm2_moe.py``, which reads the same parameter layout: ``in_proj``
columns are ``[b | c | u]``, ``conv`` is ``[W, C]`` with row ``W - 1`` on the
current token, ``wo``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import (
    Mixer, State, _dense_factory, _dtype, drawn_in, whole_array_backend,
)
from orion_tpu.ops.dispatch import causal_short_conv, decode_rows_mask
from orion_tpu.utils.profiling import scope, scoped

Array = jax.Array

# taps of the convolution (the family's conv_L_cache)
WIDTH = 3

_scoped = scoped("gated_conv")


class GatedConv(Mixer):
    layer_type: str = "gated_conv"

    rows_in_place = True
    tail_leaves = ("conv",)

    def setup(self):
        cfg = self.cfg
        assert self.causal, "gated_conv is causal-LM only"
        assert not self.sp_local and not self.quant, (self.sp_local, self.quant)
        assert not self._sp_active(), "no sequence parallel form"
        dense = _dense_factory(cfg)
        self.in_proj = dense("in_proj", 3 * cfg.d_model)
        self.conv = self.param(
            "conv",
            drawn_in(cfg, nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1)),
            (WIDTH, cfg.d_model), _dtype(cfg.param_dtype),
        )
        self.wo = dense("wo", cfg.d_model)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        return {
            "conv": jnp.zeros((batch, (WIDTH - 1) * cfg.d_model), dtype),
        }

    # -- what every entry point shares ---------------------------------------

    def _project(self, x: Array) -> Tuple[Array, Array]:
        """x [..., D] -> (v = b * u, the conv's input; c, the output's gate)."""
        with scope("gated_conv_in"):
            b, c, u = jnp.split(self.in_proj(x), 3, axis=-1)
            return b * u, c

    def _output(self, s: Array, c: Array) -> Array:
        with scope("gated_conv_out"):
            return self.wo(c * s)

    def _conv(self, v: Array, tail: Optional[Array]) -> Array:
        with scope("gated_conv_conv"):
            return causal_short_conv(
                v, self.conv, activation=False, tail=tail,
                backend=whole_array_backend(self.cfg, self.mesh),
            )

    # -- parallel forward ---------------------------------------------------

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "gated_conv is causal-LM only"
        v, c = self._project(x)
        return self._output(self._conv(v, None), c)

    # -- prefill and its pieces -----------------------------------------------

    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        zero = self.decode_state(self.cfg, self.layer_type, x.shape[0], x.dtype)
        n = x.shape[1] if length is None else length
        return self.prefill_extend(x, zero, 0, n)

    @_scoped
    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """The piece's conv reads the tail the pieces before left; the new
        tail is the last ``W - 1`` rows of ``v`` before ``length`` (the old
        tail's, where the piece is shorter than that)."""
        del offset  # position enters through the state alone
        w1 = WIDTH - 1
        v, c = self._project(x)
        old = state["conv"].reshape(x.shape[0], w1, -1)
        out = self._output(self._conv(v, old), c)
        seen = jnp.concatenate([old, v.astype(old.dtype)], axis=1)
        tail = jax.lax.dynamic_slice_in_dim(seen, length, w1, axis=1)
        return out, {"conv": tail.reshape(state["conv"].shape)}

    # -- one-token decode ---------------------------------------------------

    @_scoped
    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """Given ``rows``, the tail of a row outside the list stays as it
        was; its output is whatever the window gives and is read by nobody."""
        del t  # position enters through the state alone
        v, c = self._project(x)  # [B, C]
        ch = v.shape[-1]
        seen = jnp.concatenate(
            [state["conv"], v.astype(state["conv"].dtype)], axis=1
        )  # [B, W x C]: the window's rows side by side
        with scope("gated_conv_conv"):
            wf = self.conv.astype(jnp.float32)
            s = sum(
                seen[:, j * ch:(j + 1) * ch].astype(jnp.float32) * wf[j]
                for j in range(wf.shape[0])
            ).astype(v.dtype)
        tail = seen[:, ch:]
        if rows is not None:
            live = decode_rows_mask(rows, x.shape[0])
            tail = jnp.where(live[:, None], tail, state["conv"])
        return self._output(s, c), {"conv": tail}
