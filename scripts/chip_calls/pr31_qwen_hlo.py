"""PR 31 (no chip): compile qwen25-3b's token-generation and CTE[256] programs for the described
v5e:2x2 from the repo given, and write their optimised HLO text with what differs from process to
process or with the sources' line numbers blanked, so that two trees' programs compare with `cmp`.

    python3 scripts/chip_calls/pr31_qwen_hlo.py <repo root> <output prefix>
"""
import importlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
root, out = sys.argv[1], sys.argv[2]
sys.path[:0] = [root, os.path.join(root, "tests", "unit")]

from jax.experimental import topologies  # noqa: E402

tc = importlib.import_module("test_chip_compile")
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
app = tc._paged_app(
    tc.QWEN25_3B, topo.devices[:1], batch_size=tc.ROWS, ctx_batch_size=1, tkg_batch_size=tc.ROWS,
    seq_len=tc.WINDOW, max_context_length=tc.CTE_BUCKET, context_encoding_buckets=[tc.CTE_BUCKET],
    pa_block_size=tc.BLOCK, pa_num_blocks=tc.POOL_BLOCKS,
    attn_kernel_enabled=True, attn_block_tkg_kernel_enabled=True,
)
cache = app._cache_struct()
# the frame table (its headers, then `<n> "<file or function>"` and `<n> {<location>}` rows at
# column 0) differs where the checkout's path or the sources' line numbers moved
FRAME_TABLE = re.compile(r'(FileNames|FunctionNames|FileLocations|StackFrames)|\d+ ["{]')
for tag in ("token_generation_model", "context_encoding_model"):
    (compiled,) = app.models[tag].aot_compile(app.build_params_struct(), cache).values()
    text = re.sub(r"__[0-9a-f]{12}_\d+", "__TOKEN", compiled.as_text())  # the per-process module token
    text = re.sub(r'source_file="[^"]*"', 'source_file=""', text)
    text = re.sub(r"stack_frame_id=\d+|source_line=\d+", "", text)
    text = "\n".join(ln for ln in text.splitlines() if not FRAME_TABLE.match(ln))
    with open(f"{out}_{tag}.txt", "w") as f:
        f.write(text)
    print(tag, len(text))
