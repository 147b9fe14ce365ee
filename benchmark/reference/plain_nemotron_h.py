"""Plain reference forward of a state-space / attention / latent-mixture
hybrid whose published layers are ONE residual step each (the nemotron_h
family), independent of ``orion_tpu``.

Straightforward ``jax.numpy`` in float32: the state-space layer as its token
recurrence (``lax.scan``), attention dense and grouped, the router's top-k by
sort, no kernels, no cache, no batching. Written as the published form: a
layer ``l`` is ``x <- x + F_l(N_l(x))`` (:func:`layer`: one norm, one ``F``,
one residual), ``F`` by the pattern's letter; ``N`` is an RMSNorm with a
weight, ``x * rsqrt(mean(x^2) + norm_eps) * w``; after the last layer one more
``N``, then the head (``[D, V]``, untied). No bias anywhere but the conv's, no
position term anywhere (the state-space layers carry position).

- ``M`` (``H`` heads of ``P``, state width ``N``, ``G`` groups, conv width
  ``W``): ``[z | xBC | dt] = u W_in`` (``H P``, ``H P + 2 G N``, ``H`` columns;
  ``xBC = [x | B | C]``). ``xBC_t = silu(b + sum_j w_j xBC_{t-(W-1)+j})`` per
  channel (causal, depthwise, zeros before the start, kernel row ``W - 1`` on
  the current token). ``dt_t = softplus(dt_t + dt_bias)`` (not clamped), ``A =
  -exp(A_log)``. Per head ``h`` of group ``g = h // (H / G)``, ``S_0 = 0 [P,
  N]``, token by token: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T``,
  ``y_t = S_t C_{g,t} + D_h x_t``. Then ``y * silu(z)`` and an RMS norm over
  EACH GROUP's ``H P / G`` channels (:func:`gated_group_norm`), one ``[H P]``
  weight, then ``W_out``.
- ``*`` (``H`` query heads over ``KV`` heads x ``head_dim``): ``q = u W_q``,
  ``k = u W_k``, ``v = u W_v``; query head ``h`` reads KV head ``h // (H /
  KV)``; causal ``softmax(q k^T / sqrt(head_dim)) v``; ``W_o``. No rotary, no
  q / k norm (``head_block`` query heads of a KV head at a time, so that a
  long T fits).
- ``E`` (experts in a latent): ``s = sigmoid(u W_r)`` over the router's whole
  width, the router's product in float32 whatever ``matmul_dtype`` says; the
  ``top_k`` are chosen on ``s + bias`` (``router_bias``, a buffer) by a SORT;
  ``g_e = route_scale s_e / (sum over the chosen + 1e-20)``, without the bias;
  ``l = u W_dn``; ``r = sum_e g_e W2_e relu(W1_e l)^2`` (two matrices an
  expert, no gate); ``F(u) = r W_up + relu(u Ws1)^2 Ws2`` (the shared expert
  on the full width, added as it is). Of the routed sum only the experts HELD
  here are computed, ids ``[expert_offset, expert_offset + experts_held)`` of
  the router's width: a loop over them, every token through each, weighted by
  its gate (0 where the token did not choose it); what the absent experts
  would add is left out (their chips up-project their own partial sums: the
  up-projection is linear, so the shares' outputs add to the whole layer's).

:func:`block` composes one or two such steps over the SERVED program's block
subtree (``norm1`` + ``attn``, then ``norm2`` + ``mlp`` where the block has
them): a mixer and the ``E`` after it are one block there, a mixer with no
``E`` after it a block that is a mixer alone. The mathematics is the same.

Departures from the published description, each deliberate:

- the multi-token-prediction module (``num_nextn_predict_layers`` 1) is NOT
  here: a drafting head beside the layers that greedy serving never runs;
- assumed, where the published config has no key (the family's public code,
  from memory: no network here): attention without rotary; sigmoid scoring,
  the selection bias and the sum's 1e-20 (the DeepSeek-V3 router's keys);
  the router reads ``u``, not the latent; the gated norm by groups.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs at bf16 MXU precision. Weights arrive in the
type the system holds them in and are cast to float32 at use (an expert's
inside the loop over experts), so a caller that jits ``embed``, ``block`` and
``logits`` separately holds little more than one layer's float32 weights.

``spec``: ``layer_types`` (one entry a block: ``ssm`` or ``softmax``),
``n_heads``, ``n_kv_heads``, ``head_dim``, ``ssm_heads``, ``ssm_head_dim``,
``ssm_state``, ``ssm_groups``, ``norm_eps``, ``top_k``, ``experts_held``,
``expert_offset``, ``router_width``, ``route_scale``, and optionally
``head_block`` and ``matmul_dtype``: when given (say ``float8_e4m3fn``), both
operands of every matmul but the router's and the recurrence's state update
are rounded to that type first (saturating): the model as a lower-precision
compute type would run it, for reading what a tolerance has to refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LETTERS = {"ssm": "M", "softmax": "*"}


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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# -- M: the state-space mixer ---------------------------------------------------


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


def gated_group_norm(spec, y, z, w):
    """y, z [..., H P]: the gate FIRST, then an RMS norm over each of the
    ``ssm_groups`` groups' channels, one weight over all of them."""
    g = spec["ssm_groups"]
    y = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (g, -1))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + spec["norm_eps"])
    return y.reshape(z.shape) * _f32(w)


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
    y = gated_group_norm(spec, y.reshape(b, t, d), z, p["out_norm"])
    return mm(spec, y, _f32(p["wo"]["kernel"]))


# -- *: grouped full attention with no position term ------------------------------


def full_attention(spec, p, u):
    b, t, _ = u.shape
    h, kvh, dh = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    rep = h // kvh
    q = mm(spec, u, _f32(p["wq"]["kernel"])).reshape(b, t, kvh, rep, dh)
    k = mm(spec, u, _f32(p["wk"]["kernel"])).reshape(b, t, kvh, dh)
    v = mm(spec, u, _f32(p["wv"]["kernel"])).reshape(b, t, kvh, dh)
    q = jnp.transpose(q, (0, 2, 3, 1, 4))  # [B, KV, rep, T, dh]
    k, v = (jnp.swapaxes(y, 1, 2)[:, :, None] for y in (k, v))  # [B, KV, 1, T, dh]
    keep = jnp.tril(jnp.ones((t, t), bool))
    step = spec.get("head_block") or rep
    outs = []
    for h0 in range(0, rep, step):  # a block of each KV head's query heads at a time
        s = mm(spec, q[:, :, h0:h0 + step], jnp.swapaxes(k, -1, -2)) * dh ** -0.5
        pr = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        outs.append(mm(spec, pr, v))
    o = jnp.transpose(jnp.concatenate(outs, axis=2), (0, 3, 1, 2, 4))  # [B, T, KV, rep, dh]
    return mm(spec, o.reshape(b, t, h * dh), _f32(p["wo"]["kernel"]))


# -- E: experts in a latent ---------------------------------------------------------


def routing_weights(spec, p, u):
    """[..., router_width]: each token's gate on its top_k experts (sigmoid
    scores; chosen on the scores plus the bias, by a sort; normalised over the
    chosen without the bias, times route_scale), 0 elsewhere."""
    scores = jax.nn.sigmoid(u @ _f32(p["router"]["kernel"]))
    order = jnp.argsort(-(scores + _f32(p["router_bias"])), axis=-1)
    ids = order[..., :spec["top_k"]]
    top = jnp.take_along_axis(scores, ids, axis=-1)
    top = spec["route_scale"] * top / (top.sum(-1, keepdims=True) + 1e-20)
    onehot = jax.nn.one_hot(ids, scores.shape[-1], dtype=jnp.float32)  # [..., k, R]
    return jnp.einsum("...k,...kr->...r", top, onehot)


def routed_experts(spec, p, u, lat, offset=None, held=None):
    """The part of the routed sum, IN THE LATENT, that experts ``[offset,
    offset + held)`` of the router's width give, from stacks that hold
    exactly those; ``u`` routes, ``lat = u W_dn`` is what the experts read."""
    lo = spec["expert_offset"] if offset is None else offset
    held = spec["experts_held"] if held is None else held
    w = routing_weights(spec, p, u)[..., lo:lo + held]

    def one(acc, expert):
        up, down, weight = expert
        y = mm(spec, relu2(mm(spec, lat, _f32(up))), _f32(down))
        return acc + weight[..., None] * y, None

    stacks = (p["experts_up"], p["experts_down"], jnp.moveaxis(w, -1, 0))
    return jax.lax.scan(one, jnp.zeros_like(lat), stacks)[0]


def shared_expert(spec, p, u):
    mid = relu2(mm(spec, u, _f32(p["shared_up"]["kernel"])))
    return mm(spec, mid, _f32(p["shared_down"]["kernel"]))


def latent_experts(spec, p, u, shared=True):
    """``F`` of an ``E`` layer for the experts held here; ``shared=False``
    leaves the shared expert out (another chip's share of the same layer,
    where the shared expert is counted once)."""
    lat = mm(spec, u, _f32(p["latent_down"]["kernel"]))
    y = mm(spec, routed_experts(spec, p, u, lat), _f32(p["latent_up"]["kernel"]))
    return y + shared_expert(spec, p, u) if shared else y


# -- the published form: one residual step a layer ---------------------------------

F = {"M": ssm, "*": full_attention, "E": latent_experts}


def layer(spec, letter, params, x):
    """One published layer: ``x + F(N(x))``; ``params = {"norm": the norm's
    weight, "f": F's parameters}``."""
    return x + F[letter](spec, params["f"], rms(spec, x, params["norm"]))


def embed(spec, params, tokens):
    """tokens [B, T] int -> [B, T, D] float32."""
    return _f32(params["params"]["embed"]["embedding"])[tokens]


def block(spec, kind, blk, x):
    """One block of the served program's tree as its one or two published
    layers: the mixer's step, then the experts' where the block has one."""
    x = layer(spec, LETTERS[kind], {"norm": blk["norm1"]["scale"], "f": blk["attn"]}, x)
    if "mlp" in blk:
        x = layer(spec, "E", {"norm": blk["norm2"]["scale"], "f": blk["mlp"]}, x)
    return x


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


def steps_of(spec, params):
    """The served tree as the published one-step-a-layer list: ``[(letter,
    {"norm", "f"}), ...]``, a block with a feed-forward part giving two."""
    out = []
    for i, kind in enumerate(spec["layer_types"]):
        blk = params["params"][f"block_{i}"]
        out.append((LETTERS[kind], {"norm": blk["norm1"]["scale"], "f": blk["attn"]}))
        if "mlp" in blk:
            out.append(("E", {"norm": blk["norm2"]["scale"], "f": blk["mlp"]}))
    return out


def forward_steps(spec, params, tokens):
    """:func:`forward` as the flat list of published layers."""
    x = embed(spec, params, tokens)
    for letter, p in steps_of(spec, params):
        x = layer(spec, letter, p, x)
    return logits(spec, params, x)
