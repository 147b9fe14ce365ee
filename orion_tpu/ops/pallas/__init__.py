"""Pallas TPU kernels — the hand-scheduled twins of the XLA ops.

- ``causal_dot``: chunked causal linear attention (causal_dot_product +
  kv-cumsum state), replacing the reference's CUDA kernels.
- ``flash_attention``: online-softmax attention, full-causal and
  sliding-window.
- ``decode_state``: the slot-multiplexed decode programs' (S, z) step
  for the rows live in a chunk only, in place.

Every function of this package that builds and applies a ``pl.pallas_call``
is entered through :func:`kernel_entry`, and through nothing else.
"""

import functools
import time

import jax
from jax.sharding import get_abstract_mesh, use_abstract_mesh

from orion_tpu.obs import trace as _trace


def kernel_entry(name: str, *static: str):
    """Decorator of a function that builds and applies the ``pl.pallas_call``
    named ``name`` (flash attention's backward holds two, ``flash_attn_dq``
    and ``flash_attn_dkv``, behind one delta column and one padding, and is
    ``flash_attn_bwd``): it runs under ``jax.jit(..., inline=True)`` with the
    parameters ``static`` names static (tile sizes, head counts, ``eps``,
    ``interpret``: plain ints, bools, floats, strings and tuples of them, so
    that two calls compare equal), and jax's own trace cache then keeps ONE
    jaxpr a distinct (statics, argument shapes and dtypes) in the process.
    Every further call site binds that jaxpr's equations again under its own
    name stack (inlined: the lowered program is the un-jitted one's), and the
    kernel's Python body is not traced again. When the body does run, a cache
    miss, it writes one ``compile.kernel`` event with its seconds; every call
    counts as a site (``obs.trace.compile_totals``). Both run only while jax
    traces a program. The entry carries ``kernel_name`` and, as
    ``__wrapped__``, the function as written."""

    def decorate(build):
        @functools.wraps(build)
        def traced(*args, **kwargs):
            began = time.monotonic()
            out = build(*args, **kwargs)
            _trace.compile_event("compile.kernel", began, time.monotonic() - began,
                                 fun_name=name, source="traced")
            return out

        cached = jax.jit(traced, static_argnames=static, inline=True)

        @functools.wraps(build)
        def entry(*args, **kwargs):
            _trace.kernel_call_site()
            # the trace cache keys on the abstract-mesh context too, and reads
            # "none" and the empty mesh (which jax sets around an equation it
            # evaluates again: a remat's or a custom_vjp's second pass) as two
            with use_abstract_mesh(get_abstract_mesh()):
                return cached(*args, **kwargs)

        entry.kernel_name = name
        return entry

    return decorate
