"""``kinds/serve_ref.py`` for a model whose head is its embedding.

``serve_ref.py`` reads the vocabulary's size from ``params["params"]
["lm_head_kernel"]``, which a model with ``tie_embeddings`` does not have (its
reference's ``logits`` takes ``columns`` of the embedding's rows instead).
This kind runs that code (a private instance of the module, as it does with
``kinds/serve.py``) and differs in one thing: the check is handed the
parameters with an EMPTY ``[0, V]`` array under that name, which says the
size, holds no bytes and is read by nothing; the boundary programs are lowered
again from the parameters as the server held them, without it.
"""

from __future__ import annotations

import importlib.util
import os

import harness

HEAD = "lm_head_kernel"


def private_serve_ref_kind():
    path = os.path.join(harness.HERE, "kinds", "serve_ref.py")
    spec = importlib.util.spec_from_file_location("benchmark_kinds_serve_ref_private", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def with_head_size(params: dict) -> dict:
    import jax.numpy as jnp

    inner = params["params"]
    if HEAD in inner:
        return params
    table = inner["embed"]["embedding"]
    return {**params, "params": {**inner, HEAD: jnp.zeros((0, table.shape[0]), table.dtype)}}


def without_head_size(params: dict) -> dict:
    inner = params["params"]
    if HEAD in inner and inner[HEAD].shape[0] == 0:
        return {**params, "params": {k: v for k, v in inner.items() if k != HEAD}}
    return params


def run(run: harness.Run) -> dict:
    base = private_serve_ref_kind()
    make_check, programs_text = base.make_check, base.boundary_programs_text

    def tied_check(run, keep):
        check = make_check(run, keep)
        return lambda params, *rest: check(with_head_size(params), *rest)

    base.make_check = tied_check
    base.boundary_programs_text = (
        lambda run, cfg, params: programs_text(run, cfg, without_head_size(params)))
    return base.run(run)
