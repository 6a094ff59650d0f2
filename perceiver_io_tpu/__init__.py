"""perceiver_io_tpu — a TPU-native (JAX/XLA/Pallas/pjit) Perceiver IO framework.

A from-scratch rebuild of the capabilities of the reference PyTorch/Lightning
implementation (DartingMelody/perceiver-io): generic Perceiver encoder/decoder
core with injected modality adapters, MLM pretraining, encoder transfer, and
image classification — designed TPU-first:

- pure-functional flax.linen modules jitted end-to-end,
- SPMD over a `jax.sharding.Mesh` (data/model/sequence axes) instead of DDP,
- a fused Pallas latent-attention kernel on the hot path,
- host-side data/tokenizer pipeline feeding device prefetch.

Public API mirrors the reference package surface (reference
`perceiver/__init__.py:1-13`).
"""

import time as _time

_IMPORT_START_NS = _time.monotonic_ns()  # first statement: the `import` span's start

from perceiver_io_tpu.obs.tracing import add_span as _add_span, span as _span

with _span("import", module="jax"):
    import jax as _jax

# Sharding-invariant PRNG (the modern jax default; this build ships it off):
# the same key must draw the same bits whether a step runs replicated or
# pjit-sharded — the checkpoint round-trip "restored replicated state
# continues IDENTICALLY to the live sharded run" guarantee, and the basis of
# the multi-host lockstep claims, both depend on it.
_jax.config.update("jax_threefry_partitionable", True)

from perceiver_io_tpu.models.adapters import (
    InputAdapter,
    OutputAdapter,
    ImageInputAdapter,
    TextInputAdapter,
    ClassificationOutputAdapter,
    TextOutputAdapter,
)
from perceiver_io_tpu.models.flow import (
    DenseSpatialOutputAdapter,
    OpticalFlowInputAdapter,
    build_optical_flow_model,
)
from perceiver_io_tpu.models.multimodal import (
    AudioInputAdapter,
    AudioOutputAdapter,
    MultimodalInputAdapter,
    MultimodalOutputAdapter,
    VideoInputAdapter,
    VideoOutputAdapter,
    build_multimodal_autoencoder,
)
from perceiver_io_tpu.models.perceiver import (
    PerceiverARLM,
    PerceiverEncoder,
    PerceiverDecoder,
    PerceiverIO,
    PerceiverMLM,
)
from perceiver_io_tpu.inference import (
    MLMPredictor,
    Predictor,
    export_forward,
    load_exported,
)
from perceiver_io_tpu.ops.masking import TextMasking

__version__ = "0.1.0"

__all__ = [
    "DenseSpatialOutputAdapter",
    "OpticalFlowInputAdapter",
    "build_optical_flow_model",
    "AudioInputAdapter",
    "AudioOutputAdapter",
    "MultimodalInputAdapter",
    "MultimodalOutputAdapter",
    "VideoInputAdapter",
    "VideoOutputAdapter",
    "build_multimodal_autoencoder",
    "InputAdapter",
    "OutputAdapter",
    "ImageInputAdapter",
    "TextInputAdapter",
    "ClassificationOutputAdapter",
    "TextOutputAdapter",
    "PerceiverEncoder",
    "PerceiverDecoder",
    "PerceiverIO",
    "PerceiverARLM",
    "PerceiverMLM",
    "TextMasking",
    "MLMPredictor",
    "Predictor",
    "export_forward",
    "load_exported",
]

# last statement: everything above, the third-party imports' own spans inside it
_add_span("import", _IMPORT_START_NS, _time.monotonic_ns(), module="perceiver_io_tpu")
