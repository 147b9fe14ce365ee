"""Plain reference forward of a gated delta-rule / gated softmax hybrid with
a held-experts mixture of experts, independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32, following the equations:

- embedding ``x = E[token]``, no position term; ``rms(x) = x * rsqrt(mean(x^2)
  + 1e-6)``; every norm weight is zero-centred, ``rms(x) * (1 + w)``;
- block: ``x += mixer(rms(x) (1 + w1)); x += moe(rms(x) (1 + w2))``;
- ``gated_delta`` mixer (``Hk`` key heads, ``Hv`` value heads, widths ``dk``,
  ``dv``): one projection gives ``[q | k | v | z]`` (``Hk dk``, ``Hk dk``,
  ``Hv dv``, ``Hv dv`` columns), another ``[b | a]`` (``Hv`` each). The
  ``[q | k | v]`` channels pass a causal depthwise convolution (left zero
  padding, no bias; kernel row ``W - 1`` on the current token) then SiLU.
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``;
  ``q <- l2norm(q) / sqrt(dk)``, ``k <- l2norm(k)`` (``x * rsqrt(sum x^2 +
  1e-6)``); key head ``j`` serves value heads ``j Hv/Hk ... (j + 1) Hv/Hk -
  1``. Per value head, ``S_0 = 0 [dk, dv]``, token by token:
  ``S <- exp(g_t) S; u_t = beta_t (v_t - S^T k_t); S <- S + k_t u_t^T;
  o_t = S^T q_t``. Output ``Wo flatten(w_n * rms_dv(o_t) * silu(z_t))``;
- ``gated_softmax`` mixer: ``[q | gate]`` per head from one projection, ``k``,
  ``v`` for ``Hkv`` heads; ``q <- rms(q)(1 + w_q)``, ``k <- rms(k)(1 + w_k)``
  over the head; rotary on the first ``rotary_dims`` of each head (dim ``j``
  pairs with ``j + rotary_dims/2``, angle ``t * base^(-2j/rotary_dims)``);
  KV head ``j`` serves query heads ``j H/Hkv ...``; scores ``q.k /
  sqrt(head_dim)``, causal softmax, times ``v`` (the masked T x T matrix,
  ``head_block`` heads at a time so that a long T fits); output ``Wo
  flatten(attn * sigmoid(gate))``;
- MoE, every layer: ``p = softmax(W_r x)`` over the router's whole width;
  the ``top_k`` largest, their weights divided by their sum; ``routed =
  sum_e w_e down_e(silu(gate_e x) * up_e x)`` over the chosen experts held
  here, ids ``[expert_offset, expert_offset + experts_held)`` (a loop over
  the held experts with a mask); what the absent experts would add is left
  out. ``shared = sigmoid(w_s . x) down_s(silu(gate_s x) * up_s x)``;
  ``out = routed + shared``;
- final ``rms (1 + w)``, logits against a separate head matrix ``[D, V]``.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs at bf16 MXU precision. Weights arrive in the
type the system holds them in and are cast to float32 at use.

``spec``: ``layer_types``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``rotary_dims``, ``rotary_base``, ``key_heads``, ``value_heads``,
``key_dim``, ``value_dim``, ``top_k``, ``experts_held``, ``expert_offset``,
``router_width``, and optionally ``head_block`` (query heads per T x T
block, default all) and ``matmul_dtype``: when given (say ``float8_e4m3fn``),
both operands of every matmul but the router's and the delta rule's state
update are rounded to that type first — the model as a lower-precision
compute type would run it, for reading what a tolerance has to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def mm(spec, a, b):
    """a @ b, operands rounded to spec["matmul_dtype"] when that is given."""
    low = spec.get("matmul_dtype")
    if low:
        a, b = (jnp.asarray(y, low).astype(jnp.float32) for y in (a, b))
    return a @ b


def rms(x):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS)


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
    hk, hv, dk, dv = (spec[n] for n in ("key_heads", "value_heads", "key_dim", "value_dim"))
    kd, vd = hk * dk, hv * dv
    proj = mm(spec, x, _f32(p["in_qkvz"]["kernel"]))
    qkv, z = proj[..., :2 * kd + vd], proj[..., 2 * kd + vd:]
    ba = mm(spec, x, _f32(p["in_ba"]["kernel"]))
    qkv = short_conv(qkv, _f32(p["conv"]))
    q = qkv[..., :kd].reshape(b, t, hk, dk)
    k = qkv[..., kd:2 * kd].reshape(b, t, hk, dk)
    v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[..., hv:] + _f32(p["dt_bias"]))
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    q, k = (jnp.repeat(y, hv // hk, axis=2) for y in (q, k))
    o = delta_rule(q, k, v, beta, g)
    o = rms(o) * _f32(p["out_norm"]) * jax.nn.silu(z.reshape(b, t, hv, dv))
    return mm(spec, o.reshape(b, t, vd), _f32(p["wo"]["kernel"]))


def rotate_half(x, rotary_dims, base):
    """x [B, T, H, dh]: rotary on the first rotary_dims dims, halves paired."""
    t, half = x.shape[1], rotary_dims // 2
    inv = base ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary_dims)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dims]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dims:]], axis=-1)


def gated_softmax(spec, p, x):
    b, t, _ = x.shape
    h, hkv, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    qg = mm(spec, x, _f32(p["wq"]["kernel"])).reshape(b, t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = mm(spec, x, _f32(p["wk"]["kernel"])).reshape(b, t, hkv, dh)
    v = mm(spec, x, _f32(p["wv"]["kernel"])).reshape(b, t, hkv, dh)
    q = rms(q) * (1.0 + _f32(p["q_norm"]["scale"]))
    k = rms(k) * (1.0 + _f32(p["k_norm"]["scale"]))
    q = rotate_half(q, spec["rotary_dims"], spec["rotary_base"])
    k = rotate_half(k, spec["rotary_dims"], spec["rotary_base"])
    keep = jnp.tril(jnp.ones((t, t), bool))
    group = h // hkv
    step = spec.get("head_block") or h
    outs = []
    for h0 in range(0, h, step):  # a block of query heads at a time
        heads = jnp.arange(h0, min(h, h0 + step))
        qh = jnp.swapaxes(q[:, :, heads], 1, 2)  # [B, h, T, dh]
        kh = jnp.swapaxes(k[:, :, heads // group], 1, 2)
        vh = jnp.swapaxes(v[:, :, heads // group], 1, 2)
        s = mm(spec, qh, jnp.swapaxes(kh, -1, -2)) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        outs.append(jnp.swapaxes(mm(spec, pr, vh), 1, 2))
    o = jnp.concatenate(outs, axis=2) * jax.nn.sigmoid(gate)
    return mm(spec, o.reshape(b, t, h * dh), _f32(p["wo"]["kernel"]))


def swiglu(spec, x, gate, up, down):
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


def routing_weights(spec, p, x):
    """[..., router_width]: each token's renormalised weight on its top_k
    experts, 0 elsewhere."""
    probs = jax.nn.softmax(x @ _f32(p["router"]["kernel"]), axis=-1)
    top, ids = jax.lax.top_k(probs, spec["top_k"])
    top = top / top.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(ids, probs.shape[-1], dtype=jnp.float32)  # [..., k, R]
    return jnp.einsum("...k,...kr->...r", top, onehot)


def routed_experts(spec, p, x):
    """The part of the routed sum that the experts held here give: a loop
    over the held experts, every token through each, masked by its weight."""
    lo, held = spec["expert_offset"], spec["experts_held"]
    w = routing_weights(spec, p, x)[..., lo:lo + held]

    def one(acc, expert):
        gate, up, down, weight = expert
        y = swiglu(spec, x, _f32(gate), _f32(up), _f32(down))
        return acc + weight[..., None] * y, None

    stacks = (p["experts_gate"], p["experts_up"], p["experts_down"], jnp.moveaxis(w, -1, 0))
    return jax.lax.scan(one, jnp.zeros_like(x), stacks)[0]


def shared_expert(spec, p, x):
    y = swiglu(spec, x, _f32(p["shared_gate"]["kernel"]), _f32(p["shared_up"]["kernel"]),
               _f32(p["shared_down"]["kernel"]))
    return jax.nn.sigmoid(x @ _f32(p["shared_scale"]["kernel"])) * y


def moe(spec, p, x):
    return routed_experts(spec, p, x) + shared_expert(spec, p, x)


MIXERS = {"gated_delta": gated_delta, "gated_softmax": gated_softmax}


def block(spec, kind, blk, x):
    x = x + MIXERS[kind](spec, blk["attn"], rms(x) * (1.0 + _f32(blk["norm1"]["scale"])))
    return x + moe(spec, blk["mlp"], rms(x) * (1.0 + _f32(blk["norm2"]["scale"])))


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    p = params["params"]
    x = _f32(p["embed"]["embedding"])[tokens]
    for i, kind in enumerate(spec["layer_types"]):
        x = block(spec, kind, p[f"block_{i}"], x)
    x = rms(x) * (1.0 + _f32(p["final_norm"]["scale"]))
    return mm(spec, x, _f32(p["lm_head_kernel"]))


def next_token_loss(spec, params, batch):
    """batch [B, T+1] -> mean next-token cross-entropy over B*T positions."""
    logits = forward(spec, params, batch[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch[:, 1:, None], axis=-1)
    return -picked.mean()
