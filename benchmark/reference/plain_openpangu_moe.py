"""Plain reference forward of a latent-attention mixture-of-experts model
with sandwich norms, independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32, no cache, no kernels, the EXPANDED
attention at every position, following the equations (``N`` is an RMSNorm
with its own weight, ``x * rsqrt(mean(x^2) + norm_eps) * w``):

- embedding ``x = E[token]``, no position term at the input;
- block: ``h = x + N2(attn(N1(x)))``; ``y = h + N4(F(N3(h)))`` (``norm1``,
  ``post_norm1``, ``norm2``, ``post_norm2``); ``F`` is a dense SwiGLU where
  the block's parameters hold ``gate`` / ``up`` / ``down``, the expert layer
  where they hold a router;
- attention (``H`` heads, widths ``nope``, ``rope``, ``value``, latent
  ``kv_rank``): ``c_q = N(x W_qa)``; ``q = c_q W_qb``, a head ``[q_nope |
  q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c = N(c_kv)``; ``q_rope`` and
  ``k_r`` rotated by position (interleaved pairs ``(2j, 2j + 1)`` by ``p *
  rotary_base^(-2j / rope)``; ONE ``k_rope`` for all heads); ``[k_nope_h |
  v_h] = c W_kvb`` a head; ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) +
  q_rope_h(t) . k_rope(s)) / sqrt(nope + rope)``, causal softmax, ``o_h = sum_s
  p v_h(s)``; ``out = [o_1 .. o_H] W_o`` (``head_block`` heads at a time so
  that a long T fits);
- expert layer: ``s = sigmoid(x W_r)`` over the router's whole width; the
  ``top_k`` largest; ``g_i = route_scale s_i / (sum of the chosen + 1e-20)``;
  ``y = sum_i g_i E_i(x) + E_shared(x)``, every ``E`` a SwiGLU, the shared one
  ungated. Of the routed sum only the experts HELD here are computed, ids
  ``[expert_offset, expert_offset + experts_held)``: a loop over them, every
  token through each, weighted by its gate (0 where the token did not choose
  it); what the absent experts would add is left out;
- final ``N``, logits against a separate head matrix ``[D, V]``.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs at bf16 MXU precision. Weights arrive in the
type the system holds them in and are cast to float32 at use (an expert's
inside the loop over experts), so a caller that jits ``embed``, ``block`` and
``logits`` separately holds little more than one layer's float32 weights.

``spec``: ``n_heads``, ``q_rank``, ``kv_rank``, ``nope``, ``rope``, ``value``,
``rotary_base``, ``norm_eps``, ``top_k``, ``experts_held``, ``expert_offset``,
``router_width``, ``route_scale``, ``layer_types`` (one entry a block; the
kind is not looked at), and optionally ``head_block`` and ``matmul_dtype``:
when given (say ``float8_e4m3fn``), both operands of every matmul are rounded
to that type first (saturating) — the model as a lower-precision compute type
would run it, for reading what a tolerance has to refuse. The router's own
product is NOT rounded: the system runs it in float32 whatever its compute
type, and a lowered router would move the reading by swapping experts, which
is no property of the matmul precision being read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def mm(spec, a, b):
    """a @ b, operands rounded to spec["matmul_dtype"] when that is given (a
    saturating cast, as 8-bit matmul hardware makes it)."""
    low = spec.get("matmul_dtype")
    if low:
        top = float(jnp.finfo(low).max)
        a, b = (jnp.clip(y, -top, top).astype(low).astype(jnp.float32) for y in (a, b))
    return a @ b


def rms(spec, x, w):
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + spec["norm_eps"])
    return x * scale * _f32(w)


def rotate(spec, x, positions):
    """x [..., T, rope] (T on the axis before the last): pair (2j, 2j + 1)
    rotated by positions[t] * rotary_base^(-2j / rope)."""
    rope = x.shape[-1]
    inv = spec["rotary_base"] ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def latent_attention(spec, p, x):
    b, t, _ = x.shape
    h, dn, dr, dv = spec["n_heads"], spec["nope"], spec["rope"], spec["value"]
    r = spec["kv_rank"]
    pos = jnp.arange(t)
    cq = rms(spec, mm(spec, x, _f32(p["wq_a"]["kernel"])), p["q_norm"]["scale"])
    q = mm(spec, cq, _f32(p["wq_b"]["kernel"])).reshape(b, t, h, dn + dr)
    q = jnp.swapaxes(q, 1, 2)  # [B, H, T, .]
    q_nope, q_rope = q[..., :dn], rotate(spec, q[..., dn:], pos)
    kva = mm(spec, x, _f32(p["wkv_a"]["kernel"]))
    c = rms(spec, kva[..., :r], p["kv_norm"]["scale"])
    k_rope = rotate(spec, kva[..., r:], pos)  # [B, T, rope]
    kv = mm(spec, c, _f32(p["wkv_b"])).reshape(b, t, h, dn + dv)
    kv = jnp.swapaxes(kv, 1, 2)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    keep = jnp.tril(jnp.ones((t, t), bool))
    step = spec.get("head_block") or h
    outs = []
    for h0 in range(0, h, step):  # a block of heads at a time
        s = mm(spec, q_nope[:, h0:h0 + step], jnp.swapaxes(k_nope[:, h0:h0 + step], -1, -2))
        s = s + mm(spec, q_rope[:, h0:h0 + step], jnp.swapaxes(k_rope, -1, -2)[:, None])
        pr = jax.nn.softmax(jnp.where(keep, s * (dn + dr) ** -0.5, -jnp.inf), axis=-1)
        outs.append(mm(spec, pr, v[:, h0:h0 + step]))
    o = jnp.swapaxes(jnp.concatenate(outs, axis=1), 1, 2)
    return mm(spec, o.reshape(b, t, h * dv), _f32(p["wo"]["kernel"]))


def swiglu(spec, x, gate, up, down):
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


def routing_weights(spec, p, x):
    """[..., router_width]: each token's gate on its top_k experts (sigmoid
    scores, normalised over the chosen, times route_scale), 0 elsewhere."""
    scores = jax.nn.sigmoid(x @ _f32(p["router"]["kernel"]))
    top, ids = jax.lax.top_k(scores, spec["top_k"])
    top = spec["route_scale"] * top / (top.sum(-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(ids, scores.shape[-1], dtype=jnp.float32)  # [..., k, R]
    return jnp.einsum("...k,...kr->...r", top, onehot)


def routed_experts(spec, p, x, offset=None, held=None):
    """The part of the routed sum that experts ``[offset, offset + held)`` of
    the router's width give, from stacks that hold exactly those."""
    lo = spec["expert_offset"] if offset is None else offset
    held = spec["experts_held"] if held is None else held
    w = routing_weights(spec, p, x)[..., lo:lo + held]

    def one(acc, expert):
        gate, up, down, weight = expert
        y = swiglu(spec, x, _f32(gate), _f32(up), _f32(down))
        return acc + weight[..., None] * y, None

    stacks = (p["experts_gate"], p["experts_up"], p["experts_down"], jnp.moveaxis(w, -1, 0))
    return jax.lax.scan(one, jnp.zeros_like(x), stacks)[0]


def shared_expert(spec, p, x):
    return swiglu(spec, x, _f32(p["shared_gate"]["kernel"]), _f32(p["shared_up"]["kernel"]),
                  _f32(p["shared_down"]["kernel"]))


def mlp(spec, p, x):
    if "router" in p:
        return routed_experts(spec, p, x) + shared_expert(spec, p, x)
    return swiglu(spec, x, *(_f32(p[n]["kernel"]) for n in ("gate", "up", "down")))


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    a = latent_attention(spec, blk["attn"], rms(spec, x, blk["norm1"]["scale"]))
    h = x + rms(spec, a, blk["post_norm1"]["scale"])
    f = mlp(spec, blk["mlp"], rms(spec, h, blk["norm2"]["scale"]))
    return h + rms(spec, f, blk["post_norm2"]["scale"])


def logits(spec, params, x, columns=None):
    """Final norm and head: [B, T, D] -> [B, T, V], or the head's
    ``columns = (start, size)`` only."""
    p = params["params"]
    head = p["lm_head_kernel"]
    if columns is not None:
        head = jax.lax.dynamic_slice_in_dim(head, columns[0], columns[1], axis=1)
    return mm(spec, rms(spec, x, p["final_norm"]["scale"]), _f32(head))


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    x = embed(spec, params, tokens)
    for i, kind in enumerate(spec["layer_types"]):
        x = block(spec, kind, params["params"][f"block_{i}"], x)
    return logits(spec, params, x)
