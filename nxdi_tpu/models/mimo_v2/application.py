"""MiMo-V2 application — dual-type KV cache (full + sliding-window stacks).

Reference: NeuronMiMoV2ForCausalLM (models/mimo_v2/modeling_mimo_v2.py:1265);
the reference sizes one cache at the max kv-head count across types, here
each type owns a correctly-shaped stack (``models/window_full.py``
``WindowFullApplication``: the block pool of the full layers and the window
layers' ring rows a slot under the block KV layout)."""

from __future__ import annotations

from nxdi_tpu.models.mimo_v2 import modeling_mimo_v2 as mv
from nxdi_tpu.models.window_full import WindowFullApplication


class MiMoV2Application(WindowFullApplication):
    NAME = "mimo_v2"

    def __init__(self, *args, **kwargs):
        kwargs.setdefault("model_family", mv)
        super().__init__(*args, **kwargs)
