"""Plain reference forward of a decayed-linear / block-sparse hybrid with
scaled residuals (MiniCPM-SALA's block), independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32: the linear layer token by token,
the sparse layer a tile of query rows at a time over ALL keys under the
selection's mask; no kernels, no cache, no batching. The equations:

- ``x0 = embed_scale * E[token]``; ``rms(x) w = x * rsqrt(mean(x^2) + 1e-6) * w``;
- block: ``h = x + a * mixer(rms(x) w1)``; ``y = h + a * mlp(rms(h) w2)``, ``a =
  residual_scale`` (``scale_depth / sqrt(PUBLISHED depth)``, whatever the cut);
  ``mlp(u) = W_down(silu(W_gate u) * (W_up u))``;
- logits ``= W_head(logit_scale * rms(y_L) w)``; head untied; no bias anywhere;
- ``rmsh``: RMSNorm over each head's own ``head_dim`` with ONE learned
  ``[head_dim]`` weight (``q_norm`` / ``k_norm``);
- ``decay_linear`` mixer (``H`` heads x ``dh``): ``q = rope(rmsh(W_q u))``, ``k =
  rope(rmsh(W_k u))``, ``v = W_v u``; ``rope`` rotates dim ``j`` with ``j + dh / 2``
  by ``t * base^(-2j / dh)`` over the whole head. ``S_0 = 0 [dh, dh]``, token by
  token ``S_t = lam_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(dh)``, ``lam_h =
  exp(-2^(-decay_exponent h / H))``, ``h = 1..H``. No feature map, no
  normaliser. ``out = W_o( rms(merge(o)) w_n * sigmoid(W_g u) )``, the norm over
  the merged ``H dh``. :func:`lightning_masked` is the same layer as a masked
  ``exp(-slope (i - s))`` matrix (never a ratio of powers), for the tests;
- ``block_sparse`` mixer (``H`` query heads, ``KV`` key/value heads, ``G = H /
  KV``; no rotary): ``q = rmsh(W_q u)``, ``k = rmsh(W_k u)``, ``v = W_v u``;
  pooled keys ``kp_j = mean(k[stride j : stride j + kernel])`` per KV head; for
  query position ``i`` the visible pooled keys are those with ``stride j +
  kernel <= i + 1``; ``p^h_ij = softmax_j(q^h_i . kp_j / sqrt(dh))`` over the
  visible ``j``; ``s_ij = sum_{h in group} p^h_ij``; block ``b`` = tokens ``[block b,
  block b + block)``; ``score_ib = max`` of ``s_ij`` over the visible pooled keys
  that overlap the block (``j = 4b - 1 .. 4b + 3`` at 32 / 16 / 64); forced: ``b <
  init_blocks`` and ``floor(i / block) - window / block + 1 <= b <= floor(i /
  block)``; selected = forced + the highest-scoring others at or before the
  query's own block, ``topk`` in all, ties to the lower index; ONE selection
  per (token, KV head), shared by its ``G`` heads; ``o^h_i = sum_s softmax_s(q^h_i
  . k_s / sqrt(dh)) v_s`` over ``s <= i`` in the selected blocks; where ``i + 1 <=
  dense_len`` every ``s <= i``. ``out = W_o( merge(o) * sigmoid(W_g u) )``.

Departures from the public code, on purpose: the dense / sparse switch is
taken PER POSITION (``i + 1 > dense_len``), so that a piece, a decode step
and a full forward agree whatever the chunking (the public code switches per
call on the call's length); the selector's softmax uses its exact normaliser
(the public kernels approximate its log-sum-exp from 4x coarser pooled keys).

Assumed, where the published config is silent (``benchmark/configs/
minicpm_sala.json`` says where each comes from): the decays ``lam_h``; the
output norm over the merged heads before the gate; ``rmsh`` per head with one
weight; the rotate-half pairing; the selector's seven sizes.

Callers wrap calls in ``jax.default_matmul_precision("highest")``. Weights
arrive in the type the system holds them in and are cast to float32 at use.

``spec``: ``layer_types``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``embed_scale``, ``residual_scale``, ``logit_scale``, ``decay_exponent``,
``rope_base``, ``kernel``, ``stride``, ``block``, ``init_blocks``, ``window``,
``topk``, ``dense_len``, and optionally ``query_tile`` (query rows scored at a
time, default 128) and ``matmul_dtype``: when given (say ``float8_e4m3fn``)
both operands of every matmul but the linear layer's state update are
rounded to that type first (saturating), for reading what a tolerance has to
refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


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


def rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * _f32(w)


def heads(y, n, dh):
    """[B, T, n dh] -> [B, n, T, dh]."""
    b, t, _ = y.shape
    return jnp.swapaxes(y.reshape(b, t, n, dh), 1, 2)


def rope(x, base):
    """x [B, H, T, dh] at positions 0..T-1, halves rotated."""
    dh, t = x.shape[-1], x.shape[-2]
    half = dh // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) * 2 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def slopes(spec):
    """-log lam_h, h = 1..H."""
    h = spec["n_heads"]
    return 2.0 ** (-spec["decay_exponent"] * jnp.arange(1, h + 1, dtype=jnp.float32) / h)


def lightning_qkv(spec, p, x):
    h, dh = spec["n_heads"], spec["head_dim"]
    q = rope(rms(heads(mm(spec, x, _f32(p["wq"]["kernel"])), h, dh), p["q_norm"]["scale"]), spec["rope_base"])
    k = rope(rms(heads(mm(spec, x, _f32(p["wk"]["kernel"])), h, dh), p["k_norm"]["scale"]), spec["rope_base"])
    return q, k, heads(mm(spec, x, _f32(p["wv"]["kernel"])), h, dh)


def lightning_out(spec, p, x, o):
    b, h, t, dh = o.shape
    merged = jnp.swapaxes(o, 1, 2).reshape(b, t, h * dh)
    gate = jax.nn.sigmoid(mm(spec, x, _f32(p["wg"]["kernel"])))
    return mm(spec, rms(merged, p["out_norm"]["scale"]) * gate, _f32(p["wo"]["kernel"]))


def lightning(spec, p, x):
    """The token recurrence."""
    q, k, v = lightning_qkv(spec, p, x)
    lam = jnp.exp(-slopes(spec))[None, :, None, None]

    def step(s, xs):
        qt, kt, vt = xs  # [B, H, dh]
        s = lam * s + kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s)

    b, h, _, dh = q.shape
    xs = tuple(jnp.moveaxis(y, 2, 0) for y in (q, k, v))
    o = jnp.moveaxis(jax.lax.scan(step, jnp.zeros((b, h, dh, dh), jnp.float32), xs)[1], 0, 2)
    return lightning_out(spec, p, x, o * dh ** -0.5)


def lightning_masked(spec, p, x):
    """The same layer as one masked matrix: ``o_i = sum_{s<=i} exp(-slope (i -
    s)) (q_i . k_s) v_s / sqrt(dh)``."""
    q, k, v = lightning_qkv(spec, p, x)
    t, dh = q.shape[-2], q.shape[-1]
    gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    decay = jnp.where(gap >= 0, jnp.exp(-slopes(spec)[:, None, None] * jnp.maximum(gap, 0)), 0.0)
    o = (jnp.einsum("bhid,bhsd->bhis", q, k) * decay) @ v
    return lightning_out(spec, p, x, o * dh ** -0.5)


def pooled_keys(spec, k):
    """k [B, KV, T, dh] -> [B, KV, NP, dh], the complete pooled keys."""
    kernel, stride, t = spec["kernel"], spec["stride"], k.shape[2]
    n = max(0, (t - kernel) // stride + 1)
    at = jnp.arange(n)[:, None] * stride + jnp.arange(kernel)
    return jnp.mean(k[:, :, at], axis=3)


def selected_blocks(spec, q, kp, pos, n_blocks):
    """q [B, KV, G, Q, dh] at positions ``pos`` [Q] against pooled keys ``kp``
    [B, KV, NP, dh] -> bool [B, KV, Q, n_blocks]: the blocks each (token, KV
    head) attends to past ``dense_len`` (forced + top scoring, ``topk`` in
    all)."""
    kernel, stride, block = spec["kernel"], spec["stride"], spec["block"]
    n_pooled, dh = kp.shape[2], q.shape[-1]
    b = jnp.arange(n_blocks)
    cur = (pos // block)[:, None]
    forced = (b < spec["init_blocks"]) | ((b >= cur - (spec["window"] // block - 1)) & (b <= cur))
    if n_pooled:
        visible = (jnp.arange(n_pooled) * stride + kernel) <= (pos[:, None] + 1)  # [Q, NP]
        qs, ks = low(spec, q, kp)
        s = jnp.einsum("bkgqd,bkjd->bkgqj", qs, ks) * dh ** -0.5
        s = jnp.where(visible, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(visible, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        shared = jnp.sum(e / jnp.where(den == 0, 1.0, den), axis=2)  # [B, KV, Q, NP]
        # the pooled keys that overlap block b: their tokens [stride j, stride j + kernel)
        # meet [block b, block b + block)
        ratio, reach = block // stride, kernel // stride
        j = b[:, None] * ratio - (reach - 1) + jnp.arange(ratio + reach - 1)  # [NB, 5]
        inside = (j >= 0) & (j < n_pooled)
        jc = jnp.clip(j, 0, n_pooled - 1)
        seen = inside & visible[:, jc]  # [Q, NB, 5]
        score = jnp.max(jnp.where(seen, shared[..., jc], -jnp.inf), axis=-1)  # [B, KV, Q, NB]
    else:
        score = jnp.full(q.shape[:2] + (pos.shape[0], n_blocks), -jnp.inf)
    rank = jnp.where(forced, jnp.inf, jnp.where(b <= cur, score, -jnp.inf))
    order = jnp.argsort(-rank, axis=-1, stable=True)  # ties to the lower index
    place = jnp.argsort(order, axis=-1, stable=True)  # each block's rank
    return (place < spec["topk"]) & (b <= cur)


def sparse_attention(spec, p, x):
    b, t, _ = x.shape
    h, kvh, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    g, block = h // kvh, spec["block"]
    q = rms(heads(mm(spec, x, _f32(p["wq"]["kernel"])), h, dh), p["q_norm"]["scale"])
    k = rms(heads(mm(spec, x, _f32(p["wk"]["kernel"])), kvh, dh), p["k_norm"]["scale"])
    v = heads(mm(spec, x, _f32(p["wv"]["kernel"])), kvh, dh)
    kp = pooled_keys(spec, k)
    n_blocks = -(-t // block)
    tile = min(spec.get("query_tile") or 128, t)
    pad = (-t) % tile
    qg = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(b, kvh, g, (t + pad) // tile, tile, dh)
    where = jnp.pad(jnp.arange(t), (0, pad), mode="edge").reshape(-1, tile)
    col = jnp.arange(t)

    def one(args):
        qt, pos = args  # [B, KV, G, tile, dh], [tile]
        chosen = selected_blocks(spec, qt, kp, pos, n_blocks)
        chosen = jnp.where((pos + 1 <= spec["dense_len"])[:, None], True, chosen)
        keep = jnp.repeat(chosen, block, axis=-1)[..., :t] & (col <= pos[:, None])
        qs, ks = low(spec, qt, k)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qs, ks) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep[:, :, None], s, -jnp.inf), axis=-1)
        pr, vs = low(spec, pr, v)
        return jnp.einsum("bkgqs,bksd->bkgqd", pr, vs)

    o = jax.lax.map(one, (jnp.moveaxis(qg, 3, 0), where))  # [tiles, B, KV, G, tile, dh]
    o = jnp.moveaxis(o, 0, 3).reshape(b, h, t + pad, dh)[:, :, :t]
    merged = jnp.swapaxes(o, 1, 2).reshape(b, t, h * dh)
    gate = jax.nn.sigmoid(mm(spec, x, _f32(p["wg"]["kernel"])))
    return mm(spec, merged * gate, _f32(p["wo"]["kernel"]))


def swiglu(spec, p, x):
    gate, up, down = (_f32(p[n]["kernel"]) for n in ("gate", "up", "down"))
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


MIXERS = {"decay_linear": lightning, "block_sparse": sparse_attention}


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return spec["embed_scale"] * _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    a = spec["residual_scale"]
    h = x + a * MIXERS[kind](spec, blk["attn"], rms(x, blk["norm1"]["scale"]))
    return h + a * swiglu(spec, blk["mlp"], rms(h, blk["norm2"]["scale"]))


def logits(spec, params, x, columns=None):
    """Final norm, the logit scale and the head: [B, T, D] -> [B, T, V], or
    the head's ``columns = (start, size)`` only."""
    p = params["params"]
    head = p["lm_head_kernel"]
    if columns is not None:
        head = jax.lax.dynamic_slice_in_dim(head, columns[0], columns[1], axis=1)
    return mm(spec, spec["logit_scale"] * rms(x, p["final_norm"]["scale"]), _f32(head))


def forward(spec, params, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    x = embed(spec, params, tokens)
    for i, kind in enumerate(spec["layer_types"]):
        x = block(spec, kind, params["params"][f"block_{i}"], x)
    return logits(spec, params, x)
