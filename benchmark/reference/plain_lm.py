"""Plain reference forward of the decoder LM, independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32: no kernels, no chunking, no cache,
no batching tricks. It follows the equations, not the program:

- embedding: ``x = E[token] + P[position]`` (learned absolute positions);
- pre-norm residual block: ``x += attn(rms(x)); x += mlp(rms(x))`` with
  RMSNorm ``x * rsqrt(mean(x^2) + 1e-6) * scale``;
- ``linear`` layer ("Transformers are RNNs", Katharopoulos et al. 2020):
  ``phi = elu + 1``; ``out_t = sum_{s<=t} (phi(q_t).phi(k_s)) v_s /
  (sum_{s<=t} phi(q_t).phi(k_s) + 1e-6)`` — written as the full T x T
  masked matrix, the form the recurrent state is an optimisation of;
- ``swa`` / ``softmax`` layer: rotary on q and k (pairs (2i, 2i+1), base
  10000), scores ``q.k / sqrt(dh)``, causal, and for ``swa`` only keys with
  ``t - s < window``; softmax; times v;
- SwiGLU MLP: ``down(silu(gate(x)) * up(x))``;
- final RMSNorm, logits against the tied embedding.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs at bf16 MXU precision. Weights arrive in the
type the system holds them in and are cast to float32 at use.

``spec`` is a mapping with ``n_layers``, ``n_heads``, ``head_dim``,
``layer_types`` (one of linear/swa/softmax per layer) and ``window``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6
LINEAR_EPS = 1e-6
ROTARY_BASE = 10000.0


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * _f32(scale)


def rotary(x, positions):
    """x [B, H, T, dh]; rotate pairs (2i, 2i+1) by position * base^(-2i/dh)."""
    dh = x.shape[-1]
    inv = 1.0 / (ROTARY_BASE ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T, dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def linear_attention(q, k, v):
    t = q.shape[-2]
    qf, kf = jax.nn.elu(q) + 1.0, jax.nn.elu(k) + 1.0
    a = jnp.einsum("bhtd,bhsd->bhts", qf, kf)
    a = jnp.where(jnp.tril(jnp.ones((t, t), bool)), a, 0.0)
    num = jnp.einsum("bhts,bhsd->bhtd", a, v)
    return num / (a.sum(-1, keepdims=True) + LINEAR_EPS)


def softmax_attention(q, k, v, window):
    t, dh = q.shape[-2], q.shape[-1]
    pos = jnp.arange(t)
    q, k = rotary(q, pos), rotary(k, pos)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * dh ** -0.5
    keep = pos[:, None] >= pos[None, :]
    if window is not None:
        keep &= (pos[:, None] - pos[None, :]) < window
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v)


def block(kind, window, n_heads, head_dim, blk, x):
    """One pre-norm residual block: x [B, T, D] -> [B, T, D]."""
    b, t, _ = x.shape

    def heads(y):
        return y.reshape(b, t, n_heads, head_dim).transpose(0, 2, 1, 3)

    a = blk["attn"]
    y = rms_norm(x, blk["norm1"]["scale"])
    q, k, v = (heads(y @ _f32(a[n]["kernel"])) for n in ("wq", "wk", "wv"))
    if kind == "linear":
        o = linear_attention(q, k, v)
    else:
        o = softmax_attention(q, k, v, window if kind == "swa" else None)
    x = x + o.transpose(0, 2, 1, 3).reshape(b, t, n_heads * head_dim) @ _f32(a["wo"]["kernel"])
    y = rms_norm(x, blk["norm2"]["scale"])
    m = blk["mlp"]
    gate, up = y @ _f32(m["gate"]["kernel"]), y @ _f32(m["up"]["kernel"])
    return x + (jax.nn.silu(gate) * up) @ _f32(m["down"]["kernel"])


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    p = params["params"]
    t = tokens.shape[1]
    emb = _f32(p["embed"]["embedding"])
    x = emb[tokens] + _f32(p["pos_embed"]["embedding"])[jnp.arange(t)]
    for i, kind in enumerate(spec["layer_types"]):
        x = block(kind, spec["window"], spec["n_heads"], spec["head_dim"], p[f"block_{i}"], x)
    return rms_norm(x, p["final_norm"]["scale"]) @ emb.T


def next_token_loss(spec, params, batch):
    """batch [B, T+1] -> mean next-token cross-entropy over B*T positions."""
    logits = forward(spec, params, batch[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch[:, 1:, None], axis=-1)
    return -picked.mean()
