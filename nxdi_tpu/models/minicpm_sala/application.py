"""MiniCPM-SALA application: a cache tree of three kinds under the block layout.

``k`` / ``v``: the sparse layers' block pool, by block table (the engine's
``BlockSpaceManager``, unchanged); ``kc``: their index of compressed keys, one
row a ``kernel_stride`` tokens, by slot id; ``lin_state``: the lightning layers'
float32 state, by slot id, with one spare slot for a batch's padding rows
(``SALAArch.slot_cache_keys``; models/minicpm_sala/modeling_minicpm_sala.py says
how a program reads each). A slot's index and state are rebuilt by its next
prefill, so neither has an allocator or a release rule; preemption is recompute.
What would have to SNAPSHOT or hand over a slot's state is refused by name."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec, block_kv_cache_partition_spec
from nxdi_tpu.models.minicpm_sala import modeling_minicpm_sala as sala
from nxdi_tpu.runtime.application import TpuModelForCausalLM


class MiniCPMSALAApplication(TpuModelForCausalLM):
    def __init__(self, *args, **kwargs):
        kwargs.setdefault("model_family", sala)
        super().__init__(*args, **kwargs)
        tc = self.tpu_config
        for flag, why in (
            (not tc.is_block_kv_layout, "a cache that is not the block KV layout"),
            # a prefix hit or a later chunk would need the state AT the prefix's
            # end and a selection over cached blocks
            (tc.is_prefix_caching, "prefix caching"),
            (tc.is_chunked_prefill, "chunked prefill"),
            (tc.mixed_dispatch, "mixed dispatch"),
            # the hand-off plane exports block chains; a slot's index and state are not one
            (tc.role != "unified", "prefill/decode hand-off"),
            (tc.kv_quant_config is not None, "a quantized cache"),
            (tc.async_mode, "async (device-resident) decode"),
            (tc.lora_config is not None, "LoRA serving"),
            (tc.enable_fused_speculation or tc.is_medusa, "speculative decoding"),
            (getattr(tc, "pp_degree", 1) > 1, "pipeline parallel inside a stage"),
        ):
            if flag:
                raise NotImplementedError(f"minicpm_sala does not support {why} yet")
        cfg = sala.build_arch(self.config).select
        if tc.pa_block_size != cfg.block_size:
            raise ValueError(
                f"pa_block_size {tc.pa_block_size} is not the selection's block "
                f"({cfg.block_size}): a selected block has to be a table entry"
            )

    def _interleaved_window_split(self, arch=None, family=None, config=None):
        return None

    def _cache_spec(self, family=None, config=None):
        t, tc = sala.build_arch(self.config).sparse, self.tpu_config
        return BlockKVCacheSpec(
            num_layers=t.num_layers, num_blocks=tc.pa_num_blocks, block_size=tc.pa_block_size,
            num_kv_heads=t.num_kv_heads, head_dim=t.head_dim, dtype=t.dtype,
        )

    def _slot_cache_struct(self):
        arch, tc = sala.build_arch(self.config), self.tpu_config
        slots = tc.kv_cache_batch_size + tc.kv_cache_padding_size
        t, lin = arch.sparse, arch.lightning
        spec = self._cache_spec()
        rows = -(-arch.select.index_rows(tc.seq_len) // 16) * 16  # whole tiles of rows
        return {
            "kc": jax.ShapeDtypeStruct(
                (t.num_layers, slots, rows, t.num_kv_heads, t.head_dim), spec.store_dtype
            ),
            "lin_state": jax.ShapeDtypeStruct(
                (lin.num_layers, slots + 1, lin.num_attention_heads, lin.head_dim, lin.head_dim),
                jnp.float32,
            ),
        }

    def _cache_struct(self):
        struct = super()._cache_struct()
        struct.update(self._slot_cache_struct())
        return struct

    def init_cache_host(self):
        cache = super().init_cache_host()
        for name, s in self._slot_cache_struct().items():
            cache[name] = jnp.zeros(s.shape, s.dtype)
        return cache

    def cache_partition_specs(self):
        specs = dict(block_kv_cache_partition_spec())
        specs["kc"] = specs["lin_state"] = P(None, None, None, None, None)
        return specs

    def enable_models(self) -> None:
        super().enable_models()
        for w in self.models.values():
            w.forward_fn = sala.causal_lm_forward
            w.forward_kwargs.pop("tensor_capture", None)
            w.forward_kwargs.pop("return_next_inputs", None)
            if w.forward_kwargs.pop("dp_sampling", False):
                raise NotImplementedError("minicpm_sala does not support dp_sampling yet")
