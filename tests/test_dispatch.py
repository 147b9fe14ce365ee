"""ops/dispatch.py: backend resolution and chunk defaults."""

import pytest

from orion_tpu.ops.dispatch import (
    _VALID,
    default_backend,
    resolve,
    resolve_chunk,
)


def test_resolve_unknown_backend_lists_valid_options():
    with pytest.raises(ValueError) as ei:
        resolve("cuda")
    msg = str(ei.value)
    # the error must name every valid backend and echo the bad input —
    # that's what makes the failure actionable from a config typo
    for valid in _VALID:
        assert valid in msg, (valid, msg)
    assert "'cuda'" in msg


@pytest.mark.parametrize("bad", ["", "CUDA", "Pallas", "triton", None, 42])
def test_resolve_rejects_every_non_member(bad):
    with pytest.raises(ValueError):
        resolve(bad)


def test_resolve_passthrough_and_auto():
    for b in _VALID:
        if b == "auto":
            continue
        assert resolve(b) == b
    # auto resolves to a concrete backend, never stays "auto"
    resolved = resolve("auto")
    assert resolved in _VALID and resolved != "auto"
    assert resolved == default_backend()


def test_resolve_chunk_explicit_passthrough():
    assert resolve_chunk(64, 4096, "pallas") == 64
    assert resolve_chunk(64, 4096, "xla") == 64


def test_resolve_chunk_tuned_defaults():
    # pallas sweet spot is C=512 for long T; short T falls back to one
    # sublane-aligned chunk; the xla scan default stays 128
    assert resolve_chunk(None, 4096, "pallas") == 512
    assert resolve_chunk(None, 20, "pallas") == 24  # ceil(20/8)*8
    assert resolve_chunk(None, 4096, "xla") == 128


def test_auto_on_tpu_is_the_kernel_or_an_error(monkeypatch):
    """On a TPU, ``auto`` means the Pallas kernel or an error: a kernel
    module that cannot be imported (a renamed Pallas symbol under a new
    jax is exactly an ImportError) must not hand the chip the XLA scan."""
    import builtins
    import sys
    import types

    import jax

    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(platform="tpu")]
    )
    assert resolve("auto") == "pallas"  # the kernel module imports here

    real_import = builtins.__import__

    def broken(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "orion_tpu.ops.pallas" and "causal_dot" in (fromlist or ()):
            raise ImportError("cannot import name 'TPUCompilerParams'")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.delitem(sys.modules, "orion_tpu.ops.pallas.causal_dot",
                        raising=False)
    monkeypatch.setattr(builtins, "__import__", broken)
    with pytest.raises(ImportError, match="TPUCompilerParams"):
        resolve("auto")


def test_auto_lets_a_failing_backend_raise(monkeypatch):
    """No device is an error, never a silent CPU/XLA answer."""
    import jax

    def no_backend(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        resolve("auto")
