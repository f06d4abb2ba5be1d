"""Test harness: hold JAX to the CPU backend with 8 virtual devices BEFORE it
initialises, so every sharding/mesh test runs without TPU hardware (the
driver's ``dryrun_multichip`` uses the same setting)."""

import os

# NXDI_TPU_HW_TESTS=1 opts out, letting tests/tpu/ compile and run the Mosaic
# kernels on an attached chip (one process per chip: run that directory alone).
_HW = os.environ.get("NXDI_TPU_HW_TESTS") == "1"
if not _HW:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if not _HW:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight integration tests excluded from the tier-1 "
        "run (pytest -m 'not slow')",
    )


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    try:
        import torch

        torch.manual_seed(0)
    except ImportError:
        pass
    yield


@pytest.fixture
def tiny_hf_llama():
    """Tiny random-weight HF llama (reference test strategy: 4-layer random
    models, seed pinned — test/README.md:57-66)."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    cfg = LlamaConfig(
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        vocab_size=256,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    model = LlamaForCausalLM(cfg).eval()
    return model, cfg
