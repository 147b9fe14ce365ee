"""Backend dispatch for the hot ops: backend="xla" | "pallas" | "auto".

Mirrors the reference's CUDA-vs-CPU dispatch for ``causal_dot_product``
(BASELINE.json north_star asks for the Pallas path to be "emitted through a
backend='xla' dispatch"). "auto" picks Pallas on TPU and the pure-XLA
chunked scan elsewhere (CPU/GPU and unit tests). The Pallas kernel can also
run anywhere via interpret mode (used by the parity tests).

Eleven ops dispatch here (``ssm_scan`` and ``ssm_state_step``, the
state-space layers' prompt pass and one-token step, ``causal_short_conv``,
the delta-rule and state-space layers' short conv over a sequence or a
prompt piece, ``gated_delta_qkv``, the delta rule's kernels on that conv's
output as it lies, ``gated_rms_norm``, the delta-rule layer's output gate,
and ``latent_cache_attention``, the latent layers' absorbed query over a
held latent cache, are described at their definitions):
``gated_delta_rule`` (the gated delta-rule
layers' parallel forward, with or without a state carried in and out:
under Pallas the chunked form as Mosaic kernels, forward and backward,
``ops/pallas/gated_delta.py``; the same equations as XLA fusions and a scan
otherwise), ``causal_dot_product`` (the parallel forward: training,
prefill), and the slot-multiplexed decode programs' three row-list steps,
``decode_state_step`` (the linear layers' ``(S, z)``, with
``decode_state_flush``), ``gated_delta_step`` (the delta rule's ``S``) and
``cache_attention`` (the full-attention layers' query over a held KV
cache): under Pallas row-sparse kernels that touch only the rows live in
the chunk (``ops/pallas/decode_state.py``: ``(S, z)`` read at every step
and written once a chunk, the delta rule's and the decayed ``S`` in place;
``ops/pallas/cache_attention.py``, only a row's live cache blocks), every
row in XLA otherwise.
"""

from __future__ import annotations

from typing import Optional

import jax

_VALID = ("auto", "xla", "pallas", "pallas_interpret", "eager")


def default_backend() -> str:
    """What ``auto`` means on this process's device: the Pallas kernel on a
    TPU (imported here, so a kernel module that cannot load is an error on
    the chip, never a silent XLA scan), the XLA scan elsewhere."""
    if jax.devices()[0].platform != "tpu":
        return "xla"
    from orion_tpu.ops.pallas import causal_dot  # noqa: F401

    return "pallas"


def resolve(backend: str) -> str:
    if backend not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {backend!r}")
    return default_backend() if backend == "auto" else backend


def resolve_chunk(chunk: Optional[int], t: int, backend: str) -> int:
    """Tuned default chunk for the causal linear-attention kernels.

    On-chip sweep (BENCH r2, v5e): the Pallas kernel is fastest at C=512
    for every T from 2k to 32k (grid overhead amortized, 512-wide MXU
    matmuls; C=1024 regresses); the XLA scan's sweet spot stays C=128.
    Short sequences fall back to one sublane-aligned chunk."""
    if chunk is not None:
        return chunk
    if backend.startswith("pallas"):
        return min(512, max(8, -(-t // 8) * 8))
    return 128


def causal_dot_product(
    q,
    k,
    v,
    *,
    backend: str = "auto",
    chunk: Optional[int] = None,
    return_state: bool = False,
    initial_state=None,
    decay=None,
    length=None,
):
    """Dispatch ``out[t] = sum_{s<=t}(q_t.k_s) v_s`` to the chosen backend.

    ``return_state`` additionally returns the final S = sum k_s ⊗ v_s (fp32).

    ``decay`` [H] (fp32 slopes ``-log lam_h`` over the heads axis -3) makes
    it the decayed form, ``out[t] = sum_{s<=t} lam^(t-s) (q_t.k_s) v_s`` with
    ``S_t = lam S_{t-1} + k_t ⊗ v_t`` (``ops/linear_attention.py`` section
    4): always ``(out, S)``, S after the first ``length`` rows (a traced
    count of real rows before right-padding; default all) from
    ``initial_state``; a Mosaic kernel under a Pallas backend, forward only,
    the chunked ``jnp`` form otherwise. ``decay=None`` is the path above,
    untouched.
    """
    if decay is not None:
        return _decayed_causal_dot(
            q, k, v, decay, backend, chunk, initial_state, length
        )
    # NB: `from orion_tpu.ops import linear_attention` would resolve to the
    # *function* re-exported by ops/__init__, which shadows the submodule of
    # the same name — import the callables by full dotted path instead.
    from orion_tpu.ops.linear_attention import (
        causal_dot_product_chunked,
        causal_dot_product_eager,
    )

    b = resolve(backend)
    chunk = resolve_chunk(chunk, q.shape[-2], b)
    if b == "eager":
        import jax.numpy as jnp

        out = causal_dot_product_eager(q, k, v)
        if initial_state is not None:
            inter = jnp.einsum(
                "...td,...de->...te",
                q.astype(jnp.float32),
                initial_state.astype(jnp.float32),
            )
            out = (out.astype(jnp.float32) + inter).astype(q.dtype)
        if return_state:
            s = jnp.einsum(
                "...td,...te->...de", k.astype(jnp.float32), v.astype(jnp.float32)
            )
            if initial_state is not None:
                s = s + initial_state.astype(jnp.float32)
            return out, s
        return out
    if b in ("pallas", "pallas_interpret"):
        from orion_tpu.ops.pallas import causal_dot as pcd

        return pcd.causal_dot_product_pallas(
            q,
            k,
            v,
            chunk=chunk,
            return_state=return_state,
            initial_state=initial_state,
            interpret=(b == "pallas_interpret"),
        )
    return causal_dot_product_chunked(
        q, k, v, chunk=chunk, return_state=return_state, initial_state=initial_state
    )


def _decayed_causal_dot(q, k, v, decay, backend, chunk, initial_state, length):
    b = resolve(backend)
    chunk = resolve_chunk(chunk, q.shape[-2], b)
    if b.startswith("pallas"):
        from orion_tpu.ops.pallas.causal_dot import decayed_causal_dot_pallas

        return decayed_causal_dot_pallas(
            q, k, v, decay, chunk=chunk, initial_state=initial_state,
            length=length, interpret=(b == "pallas_interpret"),
        )
    from orion_tpu.ops.linear_attention import (
        decayed_causal_dot_chunked,
        decayed_causal_dot_eager,
    )

    if b == "eager" and length is None:
        return decayed_causal_dot_eager(q, k, v, decay, initial_state)
    return decayed_causal_dot_chunked(
        q, k, v, decay, chunk=chunk, initial_state=initial_state, length=length
    )


def gated_delta_rule(
    q, k, v, beta, g, *, backend: str = "auto", initial_state=None,
    return_state: bool = False,
):
    """Dispatch the gated delta rule (``ops/gated_delta.py``) on q, k
    ``[..., Hk, T, Dk]``, v ``[..., Hv, T, Dv]``, beta, g ``[..., Hv, T]``;
    key head ``j`` serves value heads ``j * (Hv / Hk) ...``.
    ``initial_state`` ``[..., Hv, Dk, Dv]`` is the state the sequence starts
    from (zeros if None); with ``return_state`` the result is ``(out, final
    state)``, the state in fp32 (serving's prefill and its pieces).

    ``eager`` is the token-by-token recurrence. ``pallas`` and
    ``pallas_interpret`` run the chunked WY form as Mosaic kernels
    (``ops/pallas/gated_delta.py``: forward and backward, its own tiling,
    the key heads read in place; the forward with a state in or out zero-
    pads widths to whole lane tiles, the backward needs them whole). ``xla``
    (the CPU, and on the chip a training width the kernels do not take)
    runs the same form as XLA fusions and a scan in chunks of 64, on key
    heads repeated to the value heads; training, a block of batch rows at a
    time. A training call that holds the short conv's output goes through
    :func:`gated_delta_qkv` instead, whose kernels read q, k and v where
    they lie."""
    from orion_tpu.ops import gated_delta as gd

    b = resolve(backend)
    stateful = initial_state is not None or return_state
    state = dict(initial_state=initial_state, return_state=return_state) if stateful else {}
    if b.startswith("pallas"):
        from orion_tpu.ops.pallas import gated_delta as pgd

        if b == "pallas_interpret" or stateful or pgd.supports(q.shape[-1], v.shape[-1]):
            return pgd.gated_delta_rule_pallas(
                q, k, v, beta, g, interpret=(b == "pallas_interpret"), **state
            )
    group = v.shape[-3] // q.shape[-3]
    if group > 1:
        import jax.numpy as jnp

        q, k = (jnp.repeat(y, group, axis=-3) for y in (q, k))
    if b == "eager":
        return gd.gated_delta_recurrent(q, k, v, beta, g, **state)
    if stateful:
        return gd.gated_delta_chunked(q, k, v, beta, g, **state)
    return gd.gated_delta_by_rows(q, k, v, beta, g)


def gated_delta_reads_qkv(
    key_heads: int, value_heads: int, key_dim: int, value_dim: int, *, backend: str = "auto"
) -> bool:
    """Whether :func:`gated_delta_qkv` runs the kernels that read the short
    conv's output where it lies: a Pallas backend, v's columns on whole
    blocks of a key head's value heads and, compiled, head widths of whole
    lane tiles. Shapes alone decide; a caller that holds a state, or a mesh
    whose data axes split, calls :func:`gated_delta_rule`."""
    b = resolve(backend)
    if not b.startswith("pallas"):
        return False
    from orion_tpu.ops.pallas import gated_delta as pgd

    return pgd.reads_qkv(key_heads, value_heads, key_dim, value_dim) and (
        b == "pallas_interpret" or pgd.supports(key_dim, value_dim)
    )


def gated_delta_qkv(
    qkv, beta, g, *, key_heads: int, key_dim: int, value_dim: int, eps: float,
    backend: str = "auto",
):
    """Dispatch the delta-rule layer from its short conv's output to the
    rule's (``ops/gated_delta.py::gated_delta_qkv``, the specification):
    ``qkv [..., T, C]`` with columns ``[q | k | v]``, beta, g ``[..., Hv,
    T]`` -> ``o [..., Hv, T, Dv]`` head-major, no state in or out. Where
    :func:`gated_delta_reads_qkv` holds, ``pallas`` and ``pallas_interpret``
    run the rule's kernels on ``qkv`` as it lies: q, k and v are column
    blocks of the one array, the l2 norm of q and k and q's scale are formed
    in VMEM, and the backward writes ONE cotangent in ``qkv``'s layout, ``dq``
    and ``dk`` summed over a key head's value heads and passed through the
    norm's VJP before their one rounding (``ops/pallas/gated_delta.py``).
    Anything else is the specification's operands through
    :func:`gated_delta_rule`."""
    heads = dict(key_heads=key_heads, key_dim=key_dim, value_dim=value_dim, eps=eps)
    value_heads = (qkv.shape[-1] - 2 * key_heads * key_dim) // value_dim
    if gated_delta_reads_qkv(key_heads, value_heads, key_dim, value_dim, backend=backend):
        from orion_tpu.ops.pallas.gated_delta import gated_delta_qkv_pallas

        return gated_delta_qkv_pallas(
            qkv, beta, g, interpret=(resolve(backend) == "pallas_interpret"), **heads
        )
    from orion_tpu.ops.gated_delta import qkv_operands

    return gated_delta_rule(*qkv_operands(qkv, **heads), beta, g, backend=backend)


def causal_short_conv(
    x, w, activation: bool = True, tail=None, bias=None, *, backend: str = "auto"
):
    """Dispatch the short causal depthwise convolution of the delta-rule
    and state-space layers (+ SiLU) and of the gated-convolution layers
    (``activation=False``: the bare sum) over a whole sequence or a prompt piece
    (``ops/gated_delta.py::causal_short_conv``, the specification: x ``[...,
    T, C]``, w ``[W, C]``, ``tail [..., W - 1, C]`` the inputs just before
    ``x``, ``bias`` [C]). ``pallas`` and ``pallas_interpret`` run it as a
    Mosaic kernel pair, forward and backward, that reads each row once and
    makes the shifted views in VMEM (``ops/pallas/short_conv.py``), where
    the input allows: ``C`` a multiple of 128 and ``T`` a whole number of
    the kernel's time tiles (``short_conv.supports``). Anything else, and
    every other backend, is the XLA form. The one-token decode steps sum
    their window inline and do not come here."""
    b = resolve(backend)
    if b.startswith("pallas"):
        from orion_tpu.ops.pallas import short_conv as psc

        if psc.supports(x, w):
            return psc.causal_short_conv_pallas(
                x, w, activation, tail, bias, interpret=(b == "pallas_interpret")
            )
    from orion_tpu.ops.gated_delta import causal_short_conv as conv

    return conv(x, w, activation, tail, bias)


def gated_rms_norm(o, z, w, *, eps: float, backend: str = "auto"):
    """Dispatch the delta-rule layer's output gate, ``rms(o) * w * silu(z)``
    per head (``ops/gated_delta.py::gated_rms_norm``, the specification: o
    ``[..., Hv, T, Dv]`` head-major as :func:`gated_delta_rule` leaves it, z
    ``[..., T, Hv Dv]``, w ``[Dv]`` -> ``[..., T, Hv Dv]`` in z's dtype).
    ``pallas`` and ``pallas_interpret`` run it as a Mosaic kernel pair,
    forward and backward, that reads ``o`` and ``z`` once where they lie and
    writes time-major (``ops/pallas/gated_norm.py``), where the operands
    allow: ``Dv`` whole lane tiles and ``T`` at least a sublane tile
    (``gated_norm.supports``). Anything else (a decode step's one row),
    and every other backend, is the XLA form."""
    b = resolve(backend)
    if b.startswith("pallas"):
        from orion_tpu.ops.pallas import gated_norm as pgn

        if pgn.supports(o, z):
            return pgn.gated_rms_norm_pallas(
                o, z, w, eps=eps, interpret=(b == "pallas_interpret")
            )
    from orion_tpu.ops.gated_delta import gated_rms_norm as norm

    return norm(o, z, w, eps)


def row_sparse(backend: str) -> bool:
    """Whether ``backend`` runs the decode programs' row-list kernels."""
    return resolve(backend) in ("pallas", "pallas_interpret")


def decode_live_rows(mask, *, backend: str = "auto"):
    """The row list :func:`decode_state_step` takes for a chunk whose live
    rows are ``mask`` [B] — built once per chunk, outside the scan — or
    None where ``backend`` steps every row (XLA: the CPU, tp meshes). A
    caller that gets a list must not select the old state back over the
    unlisted rows of the linear layers: the kernel never touched them."""
    if not row_sparse(backend):
        return None
    from orion_tpu.ops.pallas.decode_state import live_rows

    return live_rows(mask)


def decode_rows_mask(rows, batch: int):
    """``[batch]`` bool: the rows a :func:`decode_live_rows` list names."""
    import jax.numpy as jnp

    idx, count = rows
    listed = jnp.where(jnp.arange(idx.shape[0]) < count[0], idx, batch)
    return jnp.zeros((batch,), bool).at[listed].set(True, mode="drop")


def gated_delta_step(q, k, v, beta, g, state, rows=None, *, backend: str = "auto"):
    """One decode step of the gated delta rule's state ``S [B, H, Dk, Dv]``
    (fp32): q, k ``[B, H, Dk]``, v ``[B, H, Dv]``, beta, g ``[B, H]`` ->
    ``(out [B, H, Dv], S)``. ``rows`` as for :func:`decode_state_step`:
    with a row list under a Pallas backend only the listed rows are read,
    updated and written, in place (``ops/pallas/decode_state.py``);
    otherwise every row steps (``ops/gated_delta.py::gated_delta_step``)."""
    if rows is not None and row_sparse(backend):
        from orion_tpu.ops.pallas import decode_state as pds

        return pds.gated_delta_step(
            q, k, v, beta, g, state, rows,
            interpret=(resolve(backend) == "pallas_interpret"),
        )
    from orion_tpu.ops.gated_delta import gated_delta_step as step

    return step(q, k, v, beta, g, state)


def ssm_scan(
    x, dt, a, bm, cm, *, backend: str = "auto", chunk: Optional[int] = None,
    initial_state=None, length=None,
):
    """The state-space layers' parallel forward over a prompt or a piece of
    one (``ops/ssm.py``): x ``[B, T, H, P]``, dt ``[B, T, H]``, ``a`` [H], bm,
    cm ``[B, T, G, N]`` -> (y, S ``[B, H, P, N]`` fp32 after the first
    ``length`` rows from ``initial_state``). ``eager`` is the token
    recurrence (no ``length``); every other backend runs the chunked form
    as XLA fusions and a scan over chunks: no Mosaic kernel is built for it
    (PERF.md section 7)."""
    from orion_tpu.ops import ssm

    if resolve(backend) == "eager" and length is None:
        return ssm.ssm_recurrent(x, dt, a, bm, cm, initial_state)
    return ssm.ssm_chunked(
        x, dt, a, bm, cm, chunk=chunk or ssm.DEFAULT_CHUNK,
        initial_state=initial_state, length=length,
    )


def ssm_state_step(x, dt, a, bm, cm, state, pack, rows=None, *, backend: str = "auto"):
    """One decode step of the state-space layers' held state ``S [B, H /
    pack, N, pack P]`` (fp32, ``ops.ssm.pack_state``): x ``[B, H, P]``, dt
    ``[B, H]``, bm, cm ``[B, G, N]`` -> ``(y [B, H, P], S)``. ``rows`` as
    for :func:`decode_state_step`: with a row list under a Pallas backend
    only the listed rows are read, updated and written, in place
    (``ops/pallas/ssm.py``); otherwise every row steps
    (``ops/ssm.py::ssm_step_packed``)."""
    if rows is not None and row_sparse(backend):
        from orion_tpu.ops.pallas import ssm as pssm

        return pssm.ssm_state_step(
            x, dt, a, bm, cm, state, pack, rows,
            interpret=(resolve(backend) == "pallas_interpret"),
        )
    from orion_tpu.ops.ssm import ssm_step_packed

    return ssm_step_packed(x, dt, a, bm, cm, state, pack)


def cache_attention(
    q, k_cache, v_cache, lengths, rows=None, *, backend: str = "auto", blocks=None,
    row_list=None, name: str = "cache_attention",
):
    """Decode attention of one query a sequence over the first ``lengths``
    [B] rows of its KV cache: q ``[B, H, Dh]``, caches ``[B, KV, cap, Dh]``
    (``H / KV`` query heads to each KV head, head ``h`` reading ``h // (H /
    KV)``; ``KV = H`` is a cache a query head)
    -> ``(out [B, H, Dh], lse [B, H])`` in fp32, the softmax over those
    rows applied to V and the log-sum-exp of their scaled scores. ``rows``
    as for :func:`decode_state_step`: with a row list under a Pallas
    backend a listed sequence reads only its live cache blocks and an
    unlisted one nothing (``ops/pallas/cache_attention.py``: its ``out`` is
    0 and its ``lse`` -1e30, the weight of an empty key set); otherwise
    every sequence multiplies and reduces over its whole reservation under
    a mask (``ops/softmax_attention.py::cached_attention``). A window's ring
    is such a cache at ``lengths = min(position + 1, window)``; ``name`` is
    what the kernel's call is named in a capture.

    ``blocks`` = (list ``[B, KV, L]`` int32, counts ``[B, KV]``, block rows)
    restricts each (sequence, KV head) to the first ``counts`` cache blocks
    its list names, of which rows ``< lengths`` count; the caches are then
    ``[B, KV, cap, Dh]`` and the ``H / KV`` query heads of a group share a
    list. Under a Pallas backend with a row list the kernel
    ``ops/pallas/cache_attention.py::block_attention`` fetches the listed
    blocks only; otherwise they are gathered
    (``ops/softmax_attention.py::cached_block_attention``).

    ``row_list`` = (list ``[B, L]`` int32, counts ``[B]``) restricts each
    sequence to the first ``counts`` cache ROWS its list names, one list for
    all of its KV heads; the caches are then ``[B, cap, KV Dh]``, a token's K
    (and its V) one contiguous row, and only the listed rows are read, on
    every backend (a gather: ``ops/softmax_attention.py::
    cached_row_attention``). A sequence the row list ``rows`` leaves out
    counts no row: ``(0, -1e30)``."""
    if row_list is not None:
        import jax.numpy as jnp

        from orion_tpu.ops.softmax_attention import cached_row_attention

        lists, counts = row_list
        b, kvd = k_cache.shape[0], k_cache.shape[-1]
        d = q.shape[-1]
        if rows is not None:
            counts = jnp.where(decode_rows_mask(rows, b), counts, 0)
            lists = jnp.where(counts[:, None] > 0, lists, 0)
        out, lse = cached_row_attention(
            q.reshape(b, kvd // d, -1, d), k_cache, v_cache, lists, counts
        )
        return out.reshape(b, -1, d), lse.reshape(b, -1)
    if blocks is not None:
        return _block_list_attention(
            q, k_cache, v_cache, lengths, rows, blocks, backend
        )
    if rows is not None and row_sparse(backend):
        from orion_tpu.ops.pallas import cache_attention as pca

        return pca.cache_attention(
            q, k_cache, v_cache, lengths, rows, name=name,
            interpret=(resolve(backend) == "pallas_interpret"),
        )
    import jax.numpy as jnp

    from orion_tpu.ops.softmax_attention import cached_attention

    valid = jnp.arange(k_cache.shape[-2])[None, None, :] < lengths[:, None, None]
    return cached_attention(q, k_cache, v_cache, valid, with_lse=True)


# lanes of a vector register: what the last axis of a kernel's operand is
# tiled to
_LANES = 128


def cache_copy_nbytes(tree) -> int:
    """Bytes of the copies the row-list step kernels read in place of the
    caches they are handed, for the leaves of ``tree`` (arrays or their
    shapes; a decode carry): a cache whose last axis is under a lane tile (a
    grouped KV cache of 64-wide heads, ``[B, KV, rows, 64]``) is held by the
    chip compactly, its rows as the minor axis, and :func:`cache_attention`'s
    kernel is handed a copy relaid to rows of 128 lanes, twice the cache at
    64, once a decode scan (the described chip's compile of 256 slots x 2,560
    rows: six copies of 1.25 GB beside caches of 0.67; ``tests/
    test_chip_compile.py`` pins it). The engine's memory account adds this to
    the carry (``serving/batching.py::fits_once_only``, which has the carry's
    shapes and no layer types: so by shape, and an upper bound: a narrow leaf
    that no kernel reads is never copied). Vectors (one axis) are no such
    leaf; a leaf 128 wide or more is read where it lies."""
    import jax

    return sum(
        x.size // x.shape[-1] * _LANES * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if len(x.shape) > 1 and x.shape[-1] < _LANES
    )


def latent_cache_attention(
    qt, qr, c_cache, kr_cache, lengths, rows=None, *, scale: float,
    backend: str = "auto",
):
    """Decode attention of one token a sequence over the first ``lengths``
    [B] rows of its LATENT cache (``models/mixers/latent.py``, the absorbed
    step): qt ``[B, H, R]`` and qr ``[B, H, Dr]`` are every head's query
    against the key ``[c | k_rope]`` that all heads share, caches ``[B, cap,
    R]`` and ``[B, cap, Dr]`` -> ``(u [B, H, R], lse [B, H])`` in fp32, the
    softmax of ``scale (qt . c + qr . k_rope)`` applied to ``c`` itself.
    ``rows`` as for :func:`cache_attention`: with a row list under a Pallas
    backend a listed sequence reads its live latent blocks once, for scores
    and values, and an unlisted one nothing (``ops/pallas/cache_attention.py
    ::latent_attention``; ``u`` 0 and ``lse`` -1e30 there); otherwise every
    sequence reduces over its whole reservation under a mask."""
    if rows is not None and row_sparse(backend):
        from orion_tpu.ops.pallas import cache_attention as pca

        return pca.latent_attention(
            qt, qr, c_cache, kr_cache, lengths, rows, scale=scale,
            interpret=(resolve(backend) == "pallas_interpret"),
        )
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops.softmax_attention import _NEG

    f32 = jnp.float32
    c = c_cache.astype(f32)
    s = jnp.einsum("bhc,bsc->bhs", qt.astype(f32), c)
    s = s + jnp.einsum("bhr,bsr->bhs", qr.astype(f32), kr_cache.astype(f32))
    valid = jnp.arange(c.shape[1])[None, None, :] < lengths[:, None, None]
    s = jnp.where(valid, s * scale, _NEG)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhs,bsc->bhc", jnp.exp(s - lse[..., None]), c), lse


def _block_list_attention(q, k_cache, v_cache, lengths, rows, blocks, backend):
    lists, counts, size = blocks
    b, kvh, cap, d = k_cache.shape
    qg = q.reshape(b, kvh, q.shape[1] // kvh, d)
    if rows is not None and row_sparse(backend):
        from orion_tpu.ops.pallas import cache_attention as pca

        out, lse = pca.block_attention(
            qg, k_cache, v_cache, lengths, lists, counts, rows, block=size,
            interpret=(resolve(backend) == "pallas_interpret"),
        )
    else:
        from orion_tpu.ops.softmax_attention import cached_block_attention

        out, lse = cached_block_attention(
            qg, k_cache, v_cache, lengths, lists, counts, size
        )
    return out.reshape(b, -1, d), lse.reshape(b, -1)


def decode_state_step(
    q, k, v, state, rows=None, *, backend: str = "auto", decay=None, chunk=None
):
    """One decode step of the linear layers' ``(S, z)`` state.

    ``rows`` is :func:`decode_live_rows` of the chunk's row mask, or None
    when every row steps (the lockstep programs, and every program where
    the backend is not Pallas): that is ``recurrent_step`` on all rows ->
    ``(out, (S, z))``. With a row list under a Pallas backend the state is
    only READ, for the listed rows: ``chunk`` = (kc, vc, j) holds the k and
    v rows of the scan's earlier steps and each row's step ``j`` [B] in it
    (``LinearAttention.chunk_split``), this step's k, v go to row ``j`` ->
    ``(out, (kc, vc))``, and :func:`decode_state_flush` writes the state
    once, after the scan (``ops/pallas/decode_state.py``).

    ``decay`` [H] (slopes ``-log lam_h``) makes it the decayed step with no
    normaliser: ``state`` is ``S`` alone, ``S <- lam S + k ⊗ v; out = q . S``
    -> ``(out, S)``, written at every step, in place for the listed rows
    (``ops/pallas/decode_state.py::decay_state_step``) or on every row."""
    if decay is not None:
        if rows is not None and row_sparse(backend):
            from orion_tpu.ops.pallas import decode_state as pds

            return pds.decay_state_step(
                q, k, v, state, decay, rows,
                interpret=(resolve(backend) == "pallas_interpret"),
            )
        from orion_tpu.ops.linear_attention import decayed_recurrent_step

        return decayed_recurrent_step(q, k, v, state, decay)
    if row_sparse(backend) and (rows is not None or chunk is not None):
        from orion_tpu.ops.pallas import decode_state as pds

        if rows is None or chunk is None:
            raise ValueError(
                "the row-list (S, z) step reads the chunk's own k, v rows: "
                "a row list and Mixer.chunk_split's rows go together"
            )
        kc, vc, j = chunk
        return pds.decode_state_step(
            q, k, v, state, (kc, vc), j, rows,
            interpret=(resolve(backend) == "pallas_interpret"),
        )
    from orion_tpu.ops.linear_attention import recurrent_step

    return recurrent_step(q, k, v, state)


def decode_state_flush(state, chunk, rows, *, backend: str = "auto"):
    """``(S, z)`` after a scan of :func:`decode_state_step` over a row
    list: the listed rows' ``chunk`` = (kc, vc) added in, in place, every
    other row untouched (``ops/pallas/decode_state.py``)."""
    from orion_tpu.ops.pallas import decode_state as pds

    return pds.decode_state_flush(
        state, chunk, rows, interpret=(resolve(backend) == "pallas_interpret")
    )


__all__ = [
    "cache_attention",
    "cache_copy_nbytes",
    "causal_dot_product",
    "causal_short_conv",
    "decode_live_rows",
    "decode_rows_mask",
    "decode_state_flush",
    "decode_state_step",
    "gated_delta_step",
    "default_backend",
    "gated_delta_qkv",
    "gated_delta_reads_qkv",
    "gated_delta_rule",
    "gated_rms_norm",
    "latent_cache_attention",
    "resolve",
    "resolve_chunk",
    "row_sparse",
    "ssm_scan",
    "ssm_state_step",
]
