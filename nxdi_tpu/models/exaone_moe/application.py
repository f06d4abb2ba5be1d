"""K-EXAONE application: the window/full cache tree of ``models/window_full.py``
(the full layers' block pool, the window layers' ring rows a slot), served on
the block KV layout alone."""

from __future__ import annotations

from nxdi_tpu.models.exaone_moe import modeling_exaone_moe as em
from nxdi_tpu.models.window_full import WindowFullApplication


class ExaoneMoeApplication(WindowFullApplication):
    NAME = "exaone_moe"

    def __init__(self, *args, **kwargs):
        kwargs.setdefault("model_family", em)
        super().__init__(*args, **kwargs)
        if not self.tpu_config.is_block_kv_layout:
            raise NotImplementedError(
                f"{self.NAME} does not support a cache that is not the block KV layout yet")
