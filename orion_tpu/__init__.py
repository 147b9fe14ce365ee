"""orion-tpu: a TPU-native linear-attention transformer framework.

A ground-up JAX/XLA/Pallas implementation of the capabilities of
`angeloskath/orion` (reference spec: /root/repo/BASELINE.json north_star —
the reference checkout itself was never mounted, see SURVEY.md §0):

- causal linear attention in its three equivalent forms (parallel O(T^2)
  eager reference, chunked kv-cumsum recurrence for training, O(1)-state
  recurrent form for decoding), with Pallas TPU kernels behind a
  ``backend=`` dispatch,
- softmax and sliding-window attention (flash-style Pallas kernels) for the
  LRA configs and the hybrid model family,
- ``train`` / ``generate`` entrypoints,
- data/fsdp/tensor/sequence/pipeline/expert parallelism over a
  `jax.sharding.Mesh` with XLA collectives over ICI/DCN (replacing the
  reference's NCCL wrapper), including routed-expert (MoE) models.
"""

import time as _time

# where ``setup.import`` starts (obs/trace.py); it ends where the import of
# ``orion_tpu.serving`` or ``orion_tpu.training`` does
IMPORT_STARTED = _time.monotonic()

__version__ = "0.1.0"

from orion_tpu import ops  # noqa: E402

# Lazy top-level API: heavy submodules (training pulls optax/orbax, generate
# pulls models) load on first use, keeping `import orion_tpu` light.
_LAZY = {
    "train": ("orion_tpu.train", "train"),
    "TrainConfig": ("orion_tpu.training.trainer", "TrainConfig"),
    "Trainer": ("orion_tpu.training.trainer", "Trainer"),
    "generate": ("orion_tpu.generate", "generate"),
    "SampleConfig": ("orion_tpu.generate", "SampleConfig"),
    "TransformerLM": ("orion_tpu.models.transformer", "TransformerLM"),
    "LRAClassifier": ("orion_tpu.models.classifier", "LRAClassifier"),
    "ModelConfig": ("orion_tpu.models.configs", "ModelConfig"),
    "get_config": ("orion_tpu.models.configs", "get_config"),
    "MoEMLP": ("orion_tpu.models.moe", "MoEMLP"),
    "MeshConfig": ("orion_tpu.parallel.mesh", "MeshConfig"),
    "make_mesh": ("orion_tpu.parallel.mesh", "make_mesh"),
    "register_feature_map": (
        "orion_tpu.ops.feature_maps", "register_feature_map",
    ),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        module, attr = _LAZY[name]
        return getattr(importlib.import_module(module), attr)
    raise AttributeError(f"module 'orion_tpu' has no attribute {name!r}")


__all__ = ["ops", "__version__", *_LAZY]
