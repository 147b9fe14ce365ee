"""Plain reference forward of a state-space / grouped full-attention hybrid
with muP-style multipliers, independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32, the state-space layer as its token
recurrence (``lax.scan``), the attention layer dense and grouped, no
kernels, no cache, following the equations (ISSUE 41, Tentpole section 1):

- ``x0 = embed_scale * E[token]``, no position term anywhere;
  ``rms(x) w = x * rsqrt(mean(x^2) + eps) * w``, ``eps = norm_eps``;
- block: ``h = x + residual_scale * mixer(rms(x) w1)``; ``y = h +
  residual_scale * W_down(silu(W_gate rms(h) w2) * W_up rms(h) w2)``;
- ``ssm`` mixer (``H`` heads of ``P``, state width ``N``, ``G`` groups, conv
  width ``W``): ``[z | xBC | dt] = u W_in`` (``H P``, ``H P + 2 G N``, ``H``
  columns; ``xBC = [x | B | C]``). ``xBC_t = silu(b + sum_j w_j xBC_{t-(W-1)
  +j})`` per channel (causal, depthwise, zeros before the start, kernel row
  ``W - 1`` on the current token). ``dt_t = softplus(dt_t + dt_bias)``,
  ``A = -exp(A_log)``, ``a_t = exp(dt_t A)``. Per head ``h`` of group ``g =
  h // (H / G)``, ``S_0 = 0 [P, N]``, token by token: ``S_t = a_t S_{t-1} +
  dt_t x_t B_t^T`` (``B_t = B_t^g``), ``y_t = S_t C_t + D_h x_t``. Output
  ``W_out( rms(merge(y_t) * silu(z_t)) w_norm )``: the gate FIRST, then one
  norm over the merged ``H P`` channels;
- ``softmax`` mixer (``H`` query heads over ``KV`` heads x ``head_dim``, no
  rotary, no q / k norm): ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; query
  head ``h`` reads KV head ``h // (H / KV)``; scores ``q . k * attn_scale``
  (a config value, NOT ``head_dim^-1/2``), causal softmax, times ``v``;
  output ``W_o merge(o)``;
- final ``rms(y) w``, times ``logit_scale``, logits against the TIED
  embedding ``E^T``.

Assumed (the config has no key; the Mamba-2 family's public code): ``dt`` is
not clamped; the gated norm multiplies by ``silu(z)`` before normalising;
parameter layout as ``orion_tpu/models/mixers/ssm.py`` states it.
Departure from the served system, by design: none in the equations; the
system holds ``S`` in float32 and everything else in bfloat16, runs prompts
through the chunked form and keeps a KV cache.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs at bf16 MXU precision. Weights arrive in the
type the system holds them in and are cast to float32 at use, so a caller
that jits ``embed``, ``block`` and ``logits`` separately holds one layer's
float32 weights at a time (``forward`` is their composition).

``spec``: ``layer_types``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``attn_scale``, ``ssm_heads``, ``ssm_head_dim``, ``ssm_state``,
``ssm_groups``, ``embed_scale``, ``residual_scale``, ``logit_scale``,
``norm_eps``, and optionally ``matmul_dtype``: when given (say
``float8_e4m3fn``), both operands of every matmul but the recurrence's state
update are rounded to that type first (saturating): the model as a
lower-precision compute type would run it, for reading what a tolerance has
to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def mm(spec, a, b):
    """a @ b, operands rounded to spec["matmul_dtype"] when that is given
    (a saturating cast, as 8-bit matmul hardware makes it)."""
    low = spec.get("matmul_dtype")
    if low:
        top = float(jnp.finfo(low).max)
        a, b = (jnp.clip(y, -top, top).astype(low).astype(jnp.float32) for y in (a, b))
    return a @ b


def rms(spec, x, w):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + spec["norm_eps"]
    ) * _f32(w)


def short_conv(x, w, bias):
    """x [B, T, C], w [W, C], bias [C]: y_t = bias + sum_j w[j] x_{t - (W-1)
    + j}, then SiLU."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(xp[:, j:j + t] * w[j] for j in range(width)))


def ssm_recurrence(x, dt, a, bm, cm):
    """x [B, T, H, P]; dt [B, T, H] (after softplus); a [H] (negative); bm,
    cm [B, T, G, N] -> S_t C_t [B, T, H, P], token by token."""
    b, _, h, p = x.shape
    rep = h // bm.shape[2]

    def step(s, xs):
        xt, dtt, bt, ct = xs  # [B, H, P], [B, H], [B, G, N] x 2
        bt, ct = jnp.repeat(bt, rep, axis=1), jnp.repeat(ct, rep, axis=1)  # [B, H, N]
        s = jnp.exp(dtt * a)[..., None, None] * s + (
            (dtt[..., None] * xt)[..., :, None] * bt[..., None, :]
        )
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

    s0 = jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32)
    xs = tuple(jnp.swapaxes(y, 0, 1) for y in (x, dt, bm, cm))
    return jnp.swapaxes(jax.lax.scan(step, s0, xs)[1], 0, 1)


def gated_norm(spec, y, z, w):
    """The gate FIRST, then one norm over the merged channels."""
    return rms(spec, y * jax.nn.silu(z), w)


def ssm(spec, p, u):
    b, t, _ = u.shape
    h, hp, n, g = (spec[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups"))
    d, c = h * hp, h * hp + 2 * g * n
    proj = mm(spec, u, _f32(p["in_proj"]["kernel"]))
    z, xbc, dt = proj[..., :d], proj[..., d:d + c], proj[..., d + c:]
    xbc = short_conv(xbc, _f32(p["conv"]), _f32(p["conv_bias"]))
    x = xbc[..., :d].reshape(b, t, h, hp)
    bm = xbc[..., d:d + g * n].reshape(b, t, g, n)
    cm = xbc[..., d + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
    y = ssm_recurrence(x, dt, -jnp.exp(_f32(p["A_log"])), bm, cm)
    y = y + _f32(p["D"])[:, None] * x
    y = gated_norm(spec, y.reshape(b, t, d), z, p["out_norm"])
    return mm(spec, y, _f32(p["wo"]["kernel"]))


def qkv(spec, p, u):
    """q [B, T, KV, G, dh] (query head ``h`` = KV head ``h // G``'s ``h % G``-th),
    k, v [B, T, KV, dh]: projections alone, no rotary, no norm, no bias."""
    b, t, _ = u.shape
    h, kvh, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    q = mm(spec, u, _f32(p["wq"]["kernel"])).reshape(b, t, kvh, h // kvh, dh)
    k = mm(spec, u, _f32(p["wk"]["kernel"])).reshape(b, t, kvh, dh)
    v = mm(spec, u, _f32(p["wv"]["kernel"])).reshape(b, t, kvh, dh)
    return q, k, v


def full_attention(spec, p, u):
    b, t, _ = u.shape
    h, dh = spec["n_heads"], spec["head_dim"]
    q, k, v = qkv(spec, p, u)
    q = jnp.transpose(q, (0, 2, 3, 1, 4))  # [B, KV, G, T, dh]
    k, v = (jnp.swapaxes(y, 1, 2)[:, :, None] for y in (k, v))  # [B, KV, 1, T, dh]
    s = mm(spec, q, jnp.swapaxes(k, -1, -2)) * spec["attn_scale"]
    keep = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = jnp.transpose(mm(spec, pr, v), (0, 3, 1, 2, 4))  # [B, T, KV, G, dh]
    return mm(spec, o.reshape(b, t, h * dh), _f32(p["wo"]["kernel"]))


def swiglu(spec, p, x):
    gate, up, down = (_f32(p[n]["kernel"]) for n in ("gate", "up", "down"))
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


MIXERS = {"ssm": ssm, "softmax": full_attention}


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return spec["embed_scale"] * _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    a = spec["residual_scale"]
    h = x + a * MIXERS[kind](spec, blk["attn"], rms(spec, x, blk["norm1"]["scale"]))
    return h + a * swiglu(spec, blk["mlp"], rms(spec, h, blk["norm2"]["scale"]))


def logits(spec, params, x, columns=None):
    """Final norm, the logit scale and the tied head: [B, T, D] -> [B, T,
    V], or the vocabulary's ``columns = (start, size)`` only."""
    p = params["params"]
    table = p["embed"]["embedding"]  # [V, D]
    if columns is not None:
        table = jax.lax.dynamic_slice_in_dim(table, columns[0], columns[1], axis=0)
    y = rms(spec, x, p["final_norm"]["scale"]) * spec["logit_scale"]
    return mm(spec, y, _f32(table).T)


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    x = embed(spec, params, tokens)
    for i, kind in enumerate(spec["layer_types"]):
        x = block(spec, kind, params["params"][f"block_{i}"], x)
    return logits(spec, params, x)
