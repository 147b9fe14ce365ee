"""Kernel feature maps phi(.) for linear attention.

Linear attention replaces softmax(QK^T)V with phi(Q) (phi(K)^T V), where
phi maps head vectors to a non-negative feature space. The reference ships
these as CUDA "feature-map projection" kernels (BASELINE.json north_star);
on TPU they are cheap elementwise/VPU ops that XLA fuses into the
surrounding matmuls, so the XLA path is already optimal — only FAVOR+'s
random projection involves an MXU matmul.

Provided maps:
- ``elu1``   : x -> elu(x) + 1              (default; "Transformers are RNNs")
- ``relu``   : x -> max(x, 0)
- ``sqrelu`` : x -> max(x, 0)^2
- ``exp``    : x -> exp(x)                  (fp32; no data-dependent shift)
- ``favor``  : FAVOR+ positive random features approximating the softmax
               kernel (Performer), with an orthogonal random projection.
- ``identity``

``make_feature_map(name, ...)`` returns a ``FeatureMap`` whose ``__call__``
applies the map over the last axis. All maps are shape-preserving except
``favor`` (last dim -> ``num_features``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class FeatureMap:
    """A named feature map. ``fn`` maps [..., d] -> [..., d_out]."""

    name: str
    fn: Callable[[jax.Array], jax.Array]
    out_dim: Optional[int] = None  # None = same as input

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.fn(x)


def _elu1(x):
    # elu(x) + 1 = exp(x) for x<0, x+1 for x>=0: strictly positive, smooth.
    return jax.nn.elu(x) + 1.0


def _relu(x):
    return jax.nn.relu(x)


def _sqrelu(x):
    r = jax.nn.relu(x)
    return r * r


def _exp(x):
    # Plain exp in fp32. No data-dependent stabilizer: phi must be a *fixed*
    # function — a per-vector or per-batch shift would rescale keys against
    # each other (biasing attention) and make prefill-phi differ from
    # decode-phi. exp of a normalized-head-vector coordinate is safely
    # within fp32 range.
    return jnp.exp(x.astype(jnp.float32)).astype(x.dtype)


def _orthogonal_gaussian(key: jax.Array, rows: int, cols: int) -> jax.Array:
    """Random matrix with orthogonal blocks of rows, Gaussian-normed rows.

    Standard FAVOR+ construction: stack of QR-orthogonalized Gaussian blocks,
    each row rescaled to the norm of a Gaussian vector, reducing estimator
    variance versus iid Gaussian projections.
    """
    n_blocks = -(-rows // cols)  # ceil
    keys = jax.random.split(key, n_blocks + 1)
    blocks = []
    for i in range(n_blocks):
        g = jax.random.normal(keys[i], (cols, cols), dtype=jnp.float32)
        q, _ = jnp.linalg.qr(g)
        blocks.append(q)
    w = jnp.concatenate(blocks, axis=0)[:rows]
    norms = jnp.sqrt(
        jnp.sum(
            jax.random.normal(keys[-1], (rows, cols), dtype=jnp.float32) ** 2,
            axis=-1,
            keepdims=True,
        )
    )
    return w * norms


def favor_features(
    key: jax.Array,
    dim: int,
    num_features: Optional[int] = None,
    stabilizer: float = 0.0,
) -> FeatureMap:
    """FAVOR+ positive random features for the softmax kernel (Performer).

    phi(x) = exp(w_i . x / d^(1/4)... ) — concretely, with x' = x / d^(1/4):
        phi(x)_i = exp(w_i . x' - |x'|^2 / 2 - c) / sqrt(m)
    where c stabilizes the exponent. E[phi(q).phi(k)] = exp(q.k / sqrt(d)),
    the softmax kernel without normalization.
    """
    m = num_features or dim
    w = _orthogonal_gaussian(key, m, dim)  # [m, d]

    def fn(x):
        xf = x.astype(jnp.float32) / (dim**0.25)
        proj = jnp.einsum("...d,md->...m", xf, w)
        sq = 0.5 * jnp.sum(xf * xf, axis=-1, keepdims=True)
        # ``stabilizer`` is a FIXED constant (default 0), not data-dependent:
        # phi must be the same function at prefill and decode time, and a
        # per-key rescale would reweight keys against each other and bias
        # the attention estimate. The exponent proj - sq is bounded above by
        # |w_i|^2/2 ~ d/2, within fp32 range for practical head dims; pass a
        # positive ``stabilizer`` if working far outside that regime.
        return (jnp.exp(proj - sq - stabilizer) / jnp.sqrt(m)).astype(x.dtype)

    return FeatureMap(name="favor", fn=fn, out_dim=m)


_SIMPLE = {
    "elu1": _elu1,
    "relu": _relu,
    "sqrelu": _sqrelu,
    "exp": _exp,
    "identity": lambda x: x,
}
_BUILTIN = frozenset(_SIMPLE)  # protected from re-registration; user names aren't


def register_feature_map(name: str, fn=None):
    """Register a custom elementwise feature map under ``name`` so any
    config can select it (``ModelConfig(feature_map=name)``) — the
    user-extensibility hook the reference exposes through its attention/
    feature-map registry (BASELINE.json names the feature-map projections
    as a pluggable kernel family; reference checkout never mounted —
    SURVEY.md §0). Usable directly or as a decorator:

        @register_feature_map("softplus")
        def _softplus(x):
            return jax.nn.softplus(x)

    The map must be positive-valued for causal linear attention (the
    normalizer q·z must stay > 0) and elementwise over the feature dim.
    Re-registering a BUILT-IN name raises; re-registering your own custom
    name overwrites it (notebook/REPL iteration).
    """

    def install(f):
        # "favor" and "learnable" are special-cased inside LinearAttention
        # (random features / learned projection) — registering them
        # here would be silently shadowed there, so reserve the names too
        if name in _BUILTIN or name in ("favor", "learnable"):
            raise ValueError(f"feature map {name!r} is built-in; pick a new name")
        _SIMPLE[name] = f
        return f

    return install if fn is None else install(fn)


def make_feature_map(
    name: str,
    *,
    key: Optional[jax.Array] = None,
    dim: Optional[int] = None,
    num_features: Optional[int] = None,
) -> FeatureMap:
    """Build a feature map by name (built-in or registered via
    ``register_feature_map``). ``favor`` requires ``key`` and ``dim``."""
    if name == "favor":
        if key is None or dim is None:
            raise ValueError("favor feature map requires key= and dim=")
        return favor_features(key, dim, num_features)
    if name not in _SIMPLE:
        raise ValueError(f"unknown feature map {name!r}; have {sorted(_SIMPLE)} + ['favor']")
    return FeatureMap(name=name, fn=_SIMPLE[name])


__all__ = [
    "FeatureMap",
    "make_feature_map",
    "register_feature_map",
    "favor_features",
]
