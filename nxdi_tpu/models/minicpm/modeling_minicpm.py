"""MiniCPM family — llama geometry with mu-P-style scalings.

Reference: contrib/models/MiniCPM4-8B (src/modeling_minicpm.py:196-350,
mirroring the OpenBMB remote-code MiniCPMForCausalLM): embeddings scaled by
``scale_emb``, every block output scaled by ``scale_depth / sqrt(L)`` before
the residual add (the shared residual_multiplier switch), and final logits
divided by ``hidden_size / dim_model_base`` (the logits_scaling divisor,
granite semantics)."""

from __future__ import annotations

import math

from nxdi_tpu.config import InferenceConfig
from nxdi_tpu.models import dense
from nxdi_tpu.models.base import DecoderArch

build_inv_freq = dense.build_inv_freq


class MiniCPMInferenceConfig(dense.DenseInferenceConfig):
    #: whether the family computes a ``sparse_config``'s block selection (models/minicpm_sala does)
    COMPUTES_BLOCK_SELECTION = False

    def add_derived_config(self):
        if getattr(self, "sparse_config", None) is not None and not self.COMPUTES_BLOCK_SELECTION:
            # MiniCPM4's InfLLM-V2 switch: past ``dense_len`` tokens its layers
            # read a SELECTION of blocks, which this dense family does not compute
            raise NotImplementedError(
                "minicpm4 with a sparse_config (block-sparse attention past dense_len "
                f"{self.sparse_config.get('dense_len')}) is not served by the dense family: "
                "it would attend densely in silence. Block selection lives in "
                "models/minicpm_sala (ops/block_select.py); drop sparse_config to serve "
                "the model dense at every length"
            )
        for name, default in (("scale_emb", 1.0), ("scale_depth", 1.0),
                              ("dim_model_base", None)):
            if not hasattr(self, name):
                setattr(self, name, default)
        super().add_derived_config()


def build_arch(config: InferenceConfig, **overrides) -> DecoderArch:
    dim_base = getattr(config, "dim_model_base", None) or config.hidden_size
    kwargs = dict(
        embed_scale=float(getattr(config, "scale_emb", 1.0)),
        residual_multiplier=(
            float(getattr(config, "scale_depth", 1.0))
            / math.sqrt(config.num_hidden_layers)
        ),
        logits_scaling=float(config.hidden_size) / float(dim_base),
        tie_word_embeddings=bool(getattr(config, "tie_word_embeddings", False)),
    )
    kwargs.update(overrides)
    return dense.build_arch(config, **kwargs)


def convert_hf_state_dict(state_dict, config: InferenceConfig):
    return dense.convert_hf_state_dict(state_dict, config, build_arch(config))


def param_specs(config: InferenceConfig):
    return dense.param_specs_for(build_arch(config))


def param_shape_struct(config: InferenceConfig):
    return dense.param_shape_struct(config, build_arch(config))
