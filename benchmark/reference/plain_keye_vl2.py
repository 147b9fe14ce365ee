"""Plain reference forward of an indexed-attention mixture-of-experts model
(Keye-VL-2.0-30B-A3B's language block), independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32: the attention a tile of query rows
at a time over ALL keys under the selection's mask; no kernels, no cache, no
batching of requests. The equations (``rms(x) w = x * rsqrt(mean(x^2) + 1e-6)
* w``; pre-norm blocks; no bias on any projection):

- ``x0 = E[token]``, no position term at the input;
- block: ``h = x + attn(rms(x) w1)``; ``y = h + moe(rms(h) w2)``;
- ``rmsh``: RMSNorm over each head's own ``head_dim`` with ONE learned
  ``[head_dim]`` weight (``q_norm`` / ``k_norm``); ``rot`` rotates dim ``j``
  with ``j + d / 2`` by ``t * base^(-2j / d)`` over the whole width ``d`` it is
  given; with ``u`` the normed input, ``H`` query heads, ``KV`` key / value
  heads (``G = H / KV`` query heads share one), ``IH`` index heads of ``ID``:

      q = rot(rmsh(W_q u)) [H x dh]   k = rot(rmsh(W_k u)) [KV x dh]   v = W_v u
      qI_t = rot(W_qI u_t) [IH x ID]   kI_s = rot(LN(W_kI u_s)) [ID]
      w_t = W_w u_t * IH^-1/2 * ID^-1/2 [IH]
      I_ts = sum_j w_tj relu(qI_tj . kI_s),  s <= t
      S_t  = the min(topk, t + 1) positions s <= t of largest I_ts (equal
             scores: the lower s); ONE selection a token, for all heads
      o_t^h = sum_{s in S_t} softmax_{s in S_t}(q_t^h . k_s^{h // G} / sqrt(dh)) v_s^{h // G}
      attn = W_o merge(o)

  ``LN`` is a LayerNorm with weight and bias, eps 1e-6. The k-th largest
  score of a row is read off ``lax.top_k``'s values; the entries equal to it
  are taken from the lowest position up until the count is full;
- ``moe(m)``: ``p = softmax(m W_r)`` over all experts; ``E_t`` = the ``top_k``
  largest; ``g_e = p_e / sum_{E_t} p``; ``sum_{e in E_t} g_e W_down^e(silu(W_gate^e
  m) * W_up^e m)``, no shared expert. A loop over the experts: each gathers
  the tokens that chose it into the shortest of a few static lengths that
  holds them (the last is every token), runs its SwiGLU on those rows and
  adds the gated rows back; a row of padding adds to no token;
- logits ``= W_head(rms(y_L) w)``, head untied.

Callers wrap calls in ``jax.default_matmul_precision("highest")``. Weights
arrive in the type the system holds them in and are cast to float32 at use
(an expert's inside the loop over experts), so a caller that jits ``embed``,
``block`` and ``logits`` separately holds little more than one layer's
float32 weights.

``spec``: ``layer_types`` (one entry a block; the kind is not looked at),
``n_heads``, ``n_kv_heads``, ``head_dim``, ``rope_base``, ``index_heads``,
``index_dim``, ``index_topk``, ``top_k``, and optionally ``query_tile`` (query
rows scored at a time, default 128) and ``matmul_dtype``: when given (say
``float8_e4m3fn``) both operands of every matmul are rounded to that type
first (saturating), the index scores' among them, for reading what a
tolerance has to refuse. The router's own product is NOT rounded: the system
runs it in float32 whatever its compute type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
MOE_LENGTHS = 6  # static row counts an expert's gather chooses from


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


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * _f32(p["scale"]) + _f32(p["bias"])


def heads(y, n, dh):
    """[B, T, n dh] -> [B, n, T, dh]."""
    b, t, _ = y.shape
    return jnp.swapaxes(y.reshape(b, t, n, dh), 1, 2)


def rope(x, base):
    """x [..., T, d] at positions 0..T-1 (T the last axis but one), halves
    rotated."""
    d, t = x.shape[-1], x.shape[-2]
    half = d // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# -- the indexer ----------------------------------------------------------------

activation = jax.nn.relu


def index_queries(spec, p, u):
    """[B, T, D] -> [B, IH, T, ID], rotated."""
    return rope(heads(mm(spec, u, _f32(p["wqi"]["kernel"])), spec["index_heads"],
                      spec["index_dim"]), spec["rope_base"])


def index_keys(spec, p, u):
    """[B, T, D] -> [B, T, ID]: ONE key a token, normed then rotated."""
    return rope(layer_norm(mm(spec, u, _f32(p["wki"]["kernel"])), p["ki_norm"]), spec["rope_base"])


def index_weights(spec, p, u):
    """[B, T, D] -> [B, IH, T]."""
    scale = spec["index_heads"] ** -0.5 * spec["index_dim"] ** -0.5
    return jnp.swapaxes(mm(spec, u, _f32(p["ww"]["kernel"])), 1, 2) * scale


def index_scores(spec, qi, w, ki):
    """qi [B, IH, Q, ID], w [B, IH, Q], ki [B, T, ID] -> I [B, 1, Q, T]: one
    score a (query, key), for all heads (the axis of 1)."""
    qs, ks = low(spec, qi, ki)
    s = activation(jnp.einsum("bhqd,bsd->bhqs", qs, ks))
    return jnp.einsum("bhqs,bhq->bqs", s, w)[:, None]


def selected(spec, scores, visible):
    """scores [..., Q, T], visible bool [Q, T] -> bool: of each row's
    visible entries the ``index_topk`` largest, equal scores to the lower
    position; all of them where fewer are visible."""
    k = min(spec["index_topk"], scores.shape[-1])
    scores = jnp.where(visible, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, k)[0][..., -1:]
    above = scores > kth
    equal = (scores == kth) & visible
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, axis=-1) <= room))) & visible


def indexed_attention(spec, p, u):
    b, t, _ = u.shape
    h, kvh, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    g = h // kvh
    q = rope(rms(heads(mm(spec, u, _f32(p["wq"]["kernel"])), h, dh), p["q_norm"]["scale"]),
             spec["rope_base"])
    k = rope(rms(heads(mm(spec, u, _f32(p["wk"]["kernel"])), kvh, dh), p["k_norm"]["scale"]),
             spec["rope_base"])
    v = heads(mm(spec, u, _f32(p["wv"]["kernel"])), kvh, dh)
    qi, ki, w = index_queries(spec, p, u), index_keys(spec, p, u), index_weights(spec, p, u)
    tile = min(spec.get("query_tile") or 128, t)
    pad = (-t) % tile
    n = (t + pad) // tile

    def tiles(a, axis):
        """axis T of ``a`` padded and split -> tiles leading."""
        width = [(0, 0)] * a.ndim
        width[axis] = (0, pad)
        a = jnp.pad(a, width)
        return jnp.moveaxis(a.reshape(a.shape[:axis] + (n, tile) + a.shape[axis + 1:]), axis, 0)

    where = jnp.pad(jnp.arange(t), (0, pad), mode="edge").reshape(n, tile)
    col = jnp.arange(t)

    def one(args):
        qt, qit, wt, pos = args  # [B, H, tile, dh], [B, IH, tile, ID], [B, IH, tile], [tile]
        visible = col <= pos[:, None]
        keep = selected(spec, index_scores(spec, qit, wt, ki), visible)  # [B, 1 | KV, tile, T]
        qs, ks = low(spec, qt.reshape(b, kvh, g, tile, dh), k)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qs, ks) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep[:, :, None], s, -jnp.inf), axis=-1)
        pr, vs = low(spec, pr, v)
        return jnp.einsum("bkgqs,bksd->bkgqd", pr, vs)

    o = jax.lax.map(one, (tiles(q, 2), tiles(qi, 2), tiles(w, 2), where))
    o = jnp.moveaxis(o, 0, 3).reshape(b, h, t + pad, dh)[:, :, :t]
    merged = jnp.swapaxes(o, 1, 2).reshape(b, t, h * dh)
    return mm(spec, merged, _f32(p["wo"]["kernel"]))


# -- the mixture ------------------------------------------------------------------


def routing_weights(spec, p, x):
    """[N, D] -> [N, E]: each token's gate on its top_k experts (softmax
    over all experts, renormalised over the chosen), 0 elsewhere."""
    probs = jax.nn.softmax(x @ _f32(p["router"]["kernel"]), axis=-1)
    top, ids = jax.lax.top_k(probs, spec["top_k"])
    top = top / top.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(ids, probs.shape[-1], dtype=jnp.float32)  # [N, k, E]
    return jnp.einsum("nk,nke->ne", top, onehot)


def swiglu(spec, x, gate, up, down):
    return mm(spec, jax.nn.silu(mm(spec, x, gate)) * mm(spec, x, up), down)


def experts(spec, p, x):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n = x.shape[0]
    w = routing_weights(spec, p, x)  # [N, E]
    lengths = sorted({max(1, -(-n // 2 ** i)) for i in range(MOE_LENGTHS)})

    def rows_of(length):
        def run(acc, chose, gate, up, down, weight):
            at = jnp.nonzero(chose, size=length, fill_value=n)[0]  # n: a row of padding
            xs = jnp.take(x, at, axis=0, mode="fill", fill_value=0.0)
            y = swiglu(spec, xs, _f32(gate), _f32(up), _f32(down))
            gates = jnp.take(weight, at, mode="fill", fill_value=0.0)
            return acc.at[at].add(gates[:, None] * y, mode="drop")
        return run

    branches = [rows_of(length) for length in lengths]

    def one(acc, expert):
        gate, up, down, weight = expert
        chose = weight > 0
        which = jnp.searchsorted(jnp.asarray(lengths), chose.sum(), side="left")
        return jax.lax.switch(which, branches, acc, chose, gate, up, down, weight), None

    stacks = (p["experts_gate"], p["experts_up"], p["experts_down"], w.T)
    return jax.lax.scan(one, jnp.zeros_like(x), stacks)[0].reshape(shape)


# -- the model --------------------------------------------------------------------


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    h = x + indexed_attention(spec, blk["attn"], rms(x, blk["norm1"]["scale"]))
    return h + experts(spec, blk["mlp"], rms(h, blk["norm2"]["scale"]))


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
