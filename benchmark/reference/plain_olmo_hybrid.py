"""Plain reference forward of a gated delta-rule / full-attention hybrid whose
blocks normalise each sublayer's OUTPUT, independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32, token-by-token recurrence, no
kernels, no cache, following the equations:

- embedding ``x = E[token]``, no position term anywhere;
  ``rms(x) w = x * rsqrt(mean(x^2) + 1e-6) * w``;
- block: ``h = x + rms(mixer(x)) w1``; ``y = h + rms(W_down(silu(W_gate h) *
  W_up h)) w2``;
- ``gated_delta`` mixer (``H`` heads, key width ``dk``, value width ``dv``; one
  key head a value head): ``[q~ | k~ | v~ | z] = x W_qkvz`` (``H dk``, ``H dk``,
  ``H dv``, ``H dv`` columns); ``[b | a] = x W_ba`` (``H`` each). The
  ``[q~ | k~ | v~]`` channels pass a causal depthwise convolution of width 4
  (left zero padding, no bias; kernel row 3 on the current token) then SiLU.
  Per head ``q = l2norm(q^) / sqrt(dk)``, ``k = l2norm(k^)`` (``x * rsqrt(sum
  x^2 + 1e-6)``), ``v = v^``; ``beta = beta_scale * sigmoid(b)`` (2 where the
  state's transition may have negative eigenvalues), ``g = -exp(A_log) *
  softplus(a + dt_bias)``. ``S_0 = 0 [dk, dv]``, token by token:
  ``S <- exp(g_t) S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T;
  o_t = S^T q_t``. Output ``W_o flatten(w_n * rms_dv(o_t) * silu(z_t))``;
- ``softmax`` mixer (``H`` heads x ``head_dim``, no grouping, no rotary):
  ``q = rms(x W_q) w_q``, ``k = rms(x W_k) w_k`` over the WHOLE projection,
  before the split into heads; ``v = x W_v``; scores ``q . k / sqrt(head_dim)``,
  causal softmax, times ``v`` (``head_block`` heads at a time so that a long
  T fits); output ``W_o flatten(attn)``;
- final ``rms(x) w``, logits against a separate head matrix ``[D, V]``.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs at bf16 MXU precision. Weights arrive in the
type the system holds them in and are cast to float32 at use, so a caller
that jits ``embed``, ``block`` and ``logits`` separately holds one layer's
float32 weights at a time (``forward`` is their composition).

``spec``: ``layer_types``, ``n_heads``, ``head_dim``, ``key_dim``,
``value_dim``, ``beta_scale``, and optionally ``head_block`` (query heads per
T x T block, default all) and ``matmul_dtype``: when given (say
``float8_e4m3fn``), both operands of every matmul but the delta rule's state
update are rounded to that type first (saturating) — the model as a
lower-precision compute type would run it, for reading what a tolerance has
to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def mm(spec, a, b):
    """a @ b, operands rounded to spec["matmul_dtype"] when that is given:
    a saturating cast, as 8-bit matmul hardware makes it (this model's
    output-normed blocks let the MLP's inner products pass float8_e4m3fn's
    448, which an unsaturated cast would turn into NaN)."""
    low = spec.get("matmul_dtype")
    if low:
        top = float(jnp.finfo(low).max)
        a, b = (jnp.clip(y, -top, top).astype(low).astype(jnp.float32) for y in (a, b))
    return a @ b


def rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * _f32(w)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + EPS)


def short_conv(x, w):
    """x [B, T, C], w [W, C]: y_t = sum_j w[j] x_{t - (W-1) + j}, then SiLU."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + t] * w[j] for j in range(width)))


def delta_rule(q, k, v, beta, g):
    """q, k [B, T, H, dk]; v [B, T, H, dv]; beta, g [B, T, H] -> [B, T, H, dv],
    token by token."""
    b, _, h, dk = q.shape

    def step(s, xs):
        qt, kt, vt, bt, gt = xs  # [B, H, ...]
        s = s * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    xs = tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, beta, g))
    return jnp.swapaxes(jax.lax.scan(step, s0, xs)[1], 0, 1)


def gated_delta(spec, p, x):
    b, t, _ = x.shape
    h, dk, dv = spec["n_heads"], spec["key_dim"], spec["value_dim"]
    kd, vd = h * dk, h * dv
    proj = mm(spec, x, _f32(p["in_qkvz"]["kernel"]))
    qkv, z = proj[..., :2 * kd + vd], proj[..., 2 * kd + vd:]
    ba = mm(spec, x, _f32(p["in_ba"]["kernel"]))
    qkv = short_conv(qkv, _f32(p["conv"]))
    q = qkv[..., :kd].reshape(b, t, h, dk)
    k = qkv[..., kd:2 * kd].reshape(b, t, h, dk)
    v = qkv[..., 2 * kd:].reshape(b, t, h, dv)
    beta = spec["beta_scale"] * jax.nn.sigmoid(ba[..., :h])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[..., h:] + _f32(p["dt_bias"]))
    o = delta_rule(l2norm(q) * dk ** -0.5, l2norm(k), v, beta, g)
    o = rms(o, p["out_norm"]) * jax.nn.silu(z.reshape(b, t, h, dv))
    return mm(spec, o.reshape(b, t, vd), _f32(p["wo"]["kernel"]))


def full_attention(spec, p, x):
    b, t, _ = x.shape
    h, dh = spec["n_heads"], spec["head_dim"]
    q = rms(mm(spec, x, _f32(p["wq"]["kernel"])), p["q_norm"]["scale"])
    k = rms(mm(spec, x, _f32(p["wk"]["kernel"])), p["k_norm"]["scale"])
    v = mm(spec, x, _f32(p["wv"]["kernel"]))
    q, k, v = (jnp.swapaxes(y.reshape(b, t, h, dh), 1, 2) for y in (q, k, v))  # [B, H, T, dh]
    keep = jnp.tril(jnp.ones((t, t), bool))
    step = spec.get("head_block") or h
    outs = []
    for h0 in range(0, h, step):  # a block of heads at a time
        qh, kh, vh = (y[:, h0:h0 + step] for y in (q, k, v))
        s = mm(spec, qh, jnp.swapaxes(kh, -1, -2)) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        outs.append(mm(spec, pr, vh))
    o = jnp.swapaxes(jnp.concatenate(outs, axis=1), 1, 2)
    return mm(spec, o.reshape(b, t, h * dh), _f32(p["wo"]["kernel"]))


def swiglu(spec, p, x):
    gate, up, down = (_f32(p[n]["kernel"]) for n in ("gate", "up", "down"))
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


MIXERS = {"gated_delta": gated_delta, "softmax": full_attention}


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    h = x + rms(MIXERS[kind](spec, blk["attn"], x), blk["norm1"]["scale"])
    return h + rms(swiglu(spec, blk["mlp"], h), blk["norm2"]["scale"])


def logits(spec, params, x, columns=None):
    """Final norm and head: [B, T, D] -> [B, T, V], or the head's
    ``columns = (start, size)`` only."""
    p = params["params"]
    head = p["lm_head_kernel"]
    if columns is not None:
        head = jax.lax.dynamic_slice_in_dim(head, columns[0], columns[1], axis=1)
    return mm(spec, rms(x, p["final_norm"]["scale"]), _f32(head))


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    x = embed(spec, params, tokens)
    for i, kind in enumerate(spec["layer_types"]):
        x = block(spec, kind, params["params"][f"block_{i}"], x)
    return logits(spec, params, x)
