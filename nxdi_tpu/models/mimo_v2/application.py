"""MiMo-V2 application — dual-type KV cache (full + sliding-window stacks).

Reference: NeuronMiMoV2ForCausalLM (models/mimo_v2/modeling_mimo_v2.py:1265);
the reference sizes one cache at the max kv-head count across types, here
each type owns a correctly-shaped stack.

Under the block KV layout the tree has two kinds of leaf: ``k``/``v``, the
block pool of the full layers (by block table: the engine's
``BlockSpaceManager``, unchanged), and ``k_swa``/``v_swa``, the window layers'
store of ring rows a SLOT (``MiMoV2Arch.ring_cache_keys``), addressed by the rows' slot
ids, which the engine hands the programs beside the block tables. A window
layer never attends more than ``sliding_window`` rows, so its share of the
cache is a constant a slot: no allocator, no release rule, no second table."""

from __future__ import annotations

import jax

from nxdi_tpu.kvcache.kv_cache import (
    block_kv_cache_partition_spec,
    kv_cache_partition_spec,
)
from nxdi_tpu.models.mimo_v2 import modeling_mimo_v2 as mv
from nxdi_tpu.runtime.application import TpuModelForCausalLM


#: lane tile of the TPU: a cache row is kept at a multiple of it where a
#: kernel's own block copies read it
LANES = 128


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


class MiMoV2Application(TpuModelForCausalLM):
    def __init__(self, *args, **kwargs):
        kwargs.setdefault("model_family", mv)
        super().__init__(*args, **kwargs)
        tc = self.tpu_config
        paged = tc.is_block_kv_layout
        for flag, why in (
            (tc.async_mode, "async (device-resident) decode"),
            (tc.lora_config is not None, "LoRA serving"),
            (tc.enable_fused_speculation or tc.is_medusa,
             "fused/medusa speculative decoding"),
            (getattr(tc, "pp_degree", 1) > 1, "pipeline parallel"),
            # a cache-attending prefill would have to read the window layers'
            # ring rows as well as the pool
            (tc.is_prefix_caching or tc.is_chunked_prefill, "prefix/chunked prefill"),
            (paged and tc.mixed_dispatch, "mixed dispatch over the paged layout"),
            # the hand-off plane exports block chains; a slot's ring rows are not one
            (paged and tc.role != "unified", "prefill/decode hand-off of the window store"),
            (paged and tc.kv_quant_config is not None, "a quantized paged cache"),
        ):
            if flag:
                raise NotImplementedError(f"mimo_v2 does not support {why} yet")

    def _interleaved_window_split(self, arch=None, family=None, config=None):
        return None  # mimo manages its own dual stacks (k_swa/v_swa)

    def _cache_spec(self, family=None, config=None):
        # the FULL-attention stack always keeps seq_len slots; window_sized_kv
        # shrinks only the swa stack (see _swa_cache_struct)
        arch = mv.build_arch(self.config)
        tc = self.tpu_config
        if tc.is_block_kv_layout:
            from nxdi_tpu.kvcache.kv_cache import BlockKVCacheSpec

            full = arch.full
            # the full layers' pool: a key row wider than a lane tile is kept
            # zero-padded as whole tiles, one a pool row (a 192-wide row lies
            # in 256 lanes of HBM either way; kvcache BlockKVLayout, KEY
            # TILES); values at their own width
            tiles = _round_up(full.head_dim, LANES) // LANES if full.head_dim > LANES else 1
            return BlockKVCacheSpec(
                num_layers=full.num_layers,
                num_blocks=tc.pa_num_blocks,
                block_size=tc.pa_block_size,
                num_kv_heads=full.num_kv_heads,
                head_dim=LANES if tiles > 1 else full.head_dim,
                dtype=full.dtype,
                v_head_dim=full.v_head_dim,
                key_tiles=tiles,
            )
        return arch.kv_cache_spec(
            tc.kv_cache_batch_size + tc.kv_cache_padding_size,
            tc.seq_len,
            quant_dtype=(tc.kv_quant_config.dtype if tc.kv_quant_config else None),
        )

    def _swa_cache_struct(self):
        arch = mv.build_arch(self.config)
        tc = self.tpu_config
        B = tc.kv_cache_batch_size + tc.kv_cache_padding_size
        # window_sized_kv shrinks ONLY the sliding-window stack to a W-slot
        # ring; full-attention layers keep the seq_len stack (reference:
        # per-layer window-sized cache shapes, kv_cache_manager.py:195-210)
        max_len = tc.seq_len
        if tc.is_block_kv_layout:
            # ring rows a slot: the window, rounded up to a tile (the commit
            # kernel writes lane-aligned windows of rows). A decode step
            # attends the old rows plus its fresh one, so the window's own
            # count of rows is enough
            w = arch.swa.sliding_window
            max_len = min(max_len, _round_up(w, LANES if w >= LANES else 8))
        elif getattr(tc, "window_sized_kv", False):
            # window_ring_slots over-provisions by spec_len+1 under linear
            # speculation so rejected-draft writes never clobber live rows
            max_len = min(max_len, tc.window_ring_slots)
        spec = arch.swa.kv_cache_spec(
            B, max_len,
            quant_dtype=(tc.kv_quant_config.dtype if tc.kv_quant_config else None),
        )
        return {
            "k_swa": jax.ShapeDtypeStruct(spec.shape, spec.store_dtype),
            "v_swa": jax.ShapeDtypeStruct(spec.shape_v, spec.store_dtype),
        }

    def _cache_struct(self):
        struct = super()._cache_struct()
        struct.update(self._swa_cache_struct())
        return struct

    def init_cache_host(self):
        import jax.numpy as jnp

        cache = super().init_cache_host()
        for k, s in self._swa_cache_struct().items():
            cache[k] = jnp.zeros(s.shape, s.dtype)
        return cache

    def cache_partition_specs(self):
        ring = kv_cache_partition_spec(self.tpu_config)  # (L, slots, KV, rows, D)
        specs = dict(
            block_kv_cache_partition_spec() if self.tpu_config.is_block_kv_layout else ring
        )
        specs["k_swa"], specs["v_swa"] = ring["k"], ring["v"]
        return specs

    def enable_models(self) -> None:
        super().enable_models()
        for w in self.models.values():
            w.forward_fn = mv.causal_lm_forward
            w.forward_kwargs.pop("tensor_capture", None)
            w.forward_kwargs.pop("return_next_inputs", None)
            if w.forward_kwargs.pop("dp_sampling", False):
                raise NotImplementedError(
                    "mimo_v2 does not support dp_sampling yet"
                )
