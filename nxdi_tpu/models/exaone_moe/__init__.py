from nxdi_tpu.models.exaone_moe import modeling_exaone_moe  # noqa: F401
