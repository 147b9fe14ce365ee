"""Plain reference forward of a gated short-convolution / grouped-attention
mixture-of-experts model (LFM2-8B-A1B's block, ``model_type: lfm2_moe``),
independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no batching
of requests, no sort and no buffer in the mixture (every expert computes
every token and a mask picks). The equations (``N`` is an RMSNorm with its own
weight, ``x * rsqrt(mean(x^2) + norm_eps) * w``; no bias on any projection):

- ``x0 = E[token]``, no position term at the input;
- block ``l``, two norms: ``h = x + Op_l(N1(x))``; ``y = h + F_l(N2(h))``;
  ``F`` is a dense SwiGLU where the block's parameters hold ``gate`` / ``up``
  / ``down``, the expert layer where they hold a router;
- a ``conv_layers`` kind, the gated short convolution, ``z`` the normed input,
  ``C`` channels, ``W`` taps (the rows of the block's ``conv`` [W, C]):

      [b | c | u] = W_in z                 C | C | C, split in that order
      v_t = b_t * u_t
      s_t = sum_{j=0..W-1} w_j * v_{t-(W-1)+j}    depthwise, causal, w_{W-1}
                                           on the current token; what lies
                                           before the sequence is zeros;
                                           NO bias and NO activation
      Op(z)_t = W_out (c_t * s_t)

- any other kind, full attention: ``H`` query heads over ``KV`` key / value
  heads of ``dh`` (``G = H / KV`` query heads share one):

      q = W_q z   k = W_k z   v = W_v z
      q, k RMS-normed over each head's own dh with ONE learned [dh] weight
      (``q_norm`` / ``k_norm``), THEN rotated over the whole head, pair (2j,
      2j + 1) by ``t * rope_base^(-2j / dh)``
      o_i^h = sum_{j <= i} softmax_j(q_i^h . k_j^{h // G} / sqrt(dh)) v_j^{h // G}
      Op(z) = W_o merge(o)                 no gate, no window

- expert layer: ``s = sigmoid(z W_r)`` over all experts; the chosen set ``C``
  is the ``top_k`` largest of ``s + b``, ``b`` the per-expert buffer
  ``router_bias``; ``g_e = route_scale s_e / (sum_C s + gate_eps)`` for ``e``
  in ``C`` (the bias is NOT in the weights; ``gate_eps`` 1e-6 as published);
  ``F(z) = sum_{e in C} g_e E_e(z)``, every ``E`` a SwiGLU; no shared expert;
- logits ``= N(y_L) E^T``: one more norm (the family's ``embedding_norm``),
  then the head, which is the embedding's table (tied).

Departures from the published description, each noted at its line: the
rotary pairing is this repository's (interleaved pairs; immaterial under
random weights); the head is TIED (the catalog's row does not carry
``tie_word_embeddings``; the family ties, and only then do the parameters
come to the card's 8.3B); the split order ``[b | c | u]``, the bare conv
(no SiLU), the ``1e-6`` and the bias's use for selection alone are the
family's modelling code as remembered (no network here).

Callers wrap calls in ``jax.default_matmul_precision("highest")``. Weights
arrive in the type the system holds them in and are cast to float32 at use
(an expert's inside the loop over experts), so a caller that jits ``embed``,
``block`` and ``logits`` separately holds little more than one layer's
float32 weights.

``spec``: ``layer_types`` (one entry a block), ``conv_layers`` (a comma list
of the kinds that are the convolution), ``n_heads``, ``n_kv_heads``,
``head_dim``, ``rope_base``, ``norm_eps``, ``top_k``, ``route_scale``,
``gate_eps``, and optionally ``query_tile`` (query rows scored at a time,
default 128) and ``matmul_dtype``: when given (say ``float8_e4m3fn``) both
operands of every matmul are rounded to that type first (saturating), for
reading what a tolerance has to refuse. The router's own product is NOT
rounded: the system runs it in float32 whatever its compute type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def low(spec, *xs):
    """Operands rounded to spec["matmul_dtype"] when given (saturating)."""
    kind = spec.get("matmul_dtype")
    if not kind:
        return xs
    top = float(jnp.finfo(kind).max)
    return tuple(jnp.clip(x, -top, top).astype(kind).astype(jnp.float32) for x in xs)


def mm(spec, a, b):
    a, b = low(spec, a, b)
    return a @ b


def rms(spec, x, w):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + spec["norm_eps"]
    ) * _f32(w)


# -- the gated short convolution ---------------------------------------------------

conv_activation = None  # the family's conv is bare: no SiLU


def short_conv(v, w):
    """v [B, T, C], w [W, C]: s_t = sum_j w[j] v_{t - (W - 1) + j}."""
    width, t = w.shape[0], v.shape[1]
    vp = jnp.pad(v, ((0, 0), (width - 1, 0), (0, 0)))
    s = sum(vp[:, j:j + t] * w[j] for j in range(width))
    return s if conv_activation is None else conv_activation(s)


def split_in(proj):
    """[..., 3 C] -> (b, c, u), in that order."""
    return jnp.split(proj, 3, axis=-1)


def gated_conv(spec, p, z):
    b, c, u = split_in(mm(spec, z, _f32(p["in_proj"]["kernel"])))
    s = short_conv(b * u, _f32(p["conv"]))
    return mm(spec, c * s, _f32(p["wo"]["kernel"]))


# -- full attention ------------------------------------------------------------------


def heads(y, n, dh):
    """[B, T, n dh] -> [B, n, T, dh]."""
    b, t, _ = y.shape
    return jnp.swapaxes(y.reshape(b, t, n, dh), 1, 2)


def rope(x, base):
    """x [..., T, d] at positions 0..T-1. Departure: dim 2j is paired with
    2j + 1 (this repository's pairing), not with j + d / 2."""
    d, t = x.shape[-1], x.shape[-2]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def attention(spec, p, z):
    b, t, _ = z.shape
    h, kvh, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    g = h // kvh
    q = rms(spec, heads(mm(spec, z, _f32(p["wq"]["kernel"])), h, dh), p["q_norm"]["scale"])
    k = rms(spec, heads(mm(spec, z, _f32(p["wk"]["kernel"])), kvh, dh), p["k_norm"]["scale"])
    v = heads(mm(spec, z, _f32(p["wv"]["kernel"])), kvh, dh)
    q, k = rope(q, spec["rope_base"]), rope(k, spec["rope_base"])
    tile = min(spec.get("query_tile") or 128, t)
    pad = (-t) % tile
    n = (t + pad) // tile
    qt = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qt = jnp.moveaxis(qt.reshape(b, kvh, g, n, tile, dh), 3, 0)  # tiles leading
    where = jnp.pad(jnp.arange(t), (0, pad), mode="edge").reshape(n, tile)

    def one(args):
        qs, rows = args  # [B, KV, G, tile, dh], [tile]
        keep = rows[:, None] >= jnp.arange(t)[None, :]
        qs, ks = low(spec, qs, k)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qs, ks) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        pr, vs = low(spec, pr, v)
        return jnp.einsum("bkgqs,bksd->bkgqd", pr, vs)

    o = jax.lax.map(one, (qt, where))
    o = jnp.moveaxis(o, 0, 3).reshape(b, h, t + pad, dh)[:, :, :t]
    merged = jnp.swapaxes(o, 1, 2).reshape(b, t, h * dh)
    return mm(spec, merged, _f32(p["wo"]["kernel"]))


def mixer(spec, kind, p, z):
    if kind in spec["conv_layers"].split(","):
        return gated_conv(spec, p, z)
    return attention(spec, p, z)


# -- the mixture ------------------------------------------------------------------


def routing_weights(spec, p, x):
    """[N, D] -> [N, E]: each token's weight on its top_k experts, 0
    elsewhere: chosen on the scores PLUS the bias, weighted by the scores
    WITHOUT it, over the chosen's sum + gate_eps, times route_scale."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]["kernel"]))
    _, ids = jax.lax.top_k(scores + _f32(p["router_bias"]), spec["top_k"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    top = spec["route_scale"] * top / (top.sum(-1, keepdims=True) + spec["gate_eps"])
    onehot = jax.nn.one_hot(ids, scores.shape[-1], dtype=jnp.float32)  # [N, k, E]
    return jnp.einsum("nk,nke->ne", top, onehot)


def swiglu(spec, x, gate, up, down):
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


def experts(spec, p, x):
    """The routed sum: EVERY expert computes every token, and its weight (0
    for a token that did not choose it) masks."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    w = routing_weights(spec, p, x)  # [N, E]

    def one(acc, expert):
        gate, up, down, weight = expert
        return acc + weight[:, None] * swiglu(spec, x, _f32(gate), _f32(up), _f32(down)), None

    stacks = (p["experts_gate"], p["experts_up"], p["experts_down"], w.T)
    return jax.lax.scan(one, jnp.zeros_like(x), stacks)[0].reshape(shape)


def mlp(spec, p, x):
    if "router" not in p:  # a leading dense layer
        return swiglu(spec, x, *(_f32(p[n]["kernel"]) for n in ("gate", "up", "down")))
    return experts(spec, p, x)


# -- the model --------------------------------------------------------------------


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    h = x + mixer(spec, kind, blk["attn"], rms(spec, x, blk["norm1"]["scale"]))
    return h + mlp(spec, blk["mlp"], rms(spec, h, blk["norm2"]["scale"]))


def logits(spec, params, x, columns=None):
    """The last norm and the tied head: [B, T, D] -> [B, T, V], or the
    vocabulary's ``columns = (start, size)`` only. Departure: the head is the
    embedding's table (see the module's docstring)."""
    p = params["params"]
    table = p["embed"]["embedding"]  # [V, D]
    if columns is not None:
        table = jax.lax.dynamic_slice_in_dim(table, columns[0], columns[1], axis=0)
    return mm(spec, rms(spec, x, p["final_norm"]["scale"]), _f32(table).T)


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    x = embed(spec, params, tokens)
    for i, kind in enumerate(spec["layer_types"]):
        x = block(spec, kind, params["params"][f"block_{i}"], x)
    return logits(spec, params, x)
