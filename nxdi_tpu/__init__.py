"""nxdi_tpu — a TPU-native LLM inference framework.

Brand-new JAX/XLA/Pallas implementation of the capability surface of
``neuronx-distributed-inference`` (AWS NxD Inference): bucketed AOT-compiled
submodels (context encoding / token generation / speculation), device-resident
KV cache, tensor/context/expert parallelism over an ICI mesh, on-device
sampling, speculative decoding, quantization, LoRA serving, and a
HuggingFace-compatible generation API. See SURVEY.md at the repo root.
"""

__version__ = "0.1.0"

from nxdi_tpu.config import (  # noqa: F401
    InferenceConfig,
    OnDeviceSamplingConfig,
    TpuConfig,
)
