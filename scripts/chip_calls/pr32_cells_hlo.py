"""PR 32 (no chip; PR 31's `pr31_qwen_hlo.py` extended to every cell): every program the benchmark's three cells compile, built from the repo given the
way `benchmark/run.py` builds them (`benchmark/serving_app.py build_app`) but for the described
v5e:2x2: `qwen25-3b` token generation at 64 rows and CTE 256 / 512 / 1024 / 2048,
`pangu-ultra-moe-ep16` token generation at 128 rows and CTE 256 / 512 / 1024. Each program's
optimised HLO text is written with what differs from process to process or with the sources' line
numbers blanked, so that two trees' programs compare with `cmp` (`pr32_hlo_cmp.sh` does both).

    python3 scripts/chip_calls/pr32_cells_hlo.py <repo root> <output directory> [<config>:<largest prompt> ...]

(PR 37: other configurations than the two of PR 32 as further arguments.)
"""
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
root, out = os.path.abspath(sys.argv[1]), sys.argv[2]
sys.path[:0] = [root, os.path.join(root, "benchmark")]
os.makedirs(out, exist_ok=True)

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402

import serving_app  # noqa: E402  (the given tree's benchmark/serving_app.py)
from nxdi_tpu.parallel.mesh import mesh_from_config  # noqa: E402

# a compile for a described chip is written to the persistent cache but cannot be read back
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
# the frame table (its headers, then `<n> "<file or function>"` and `<n> {<location>}` rows at
# column 0) differs where the checkout's path or the sources' line numbers moved
FRAME_TABLE = re.compile(r'(FileNames|FunctionNames|FileLocations|StackFrames)|\d+ ["{]')
# the cells' largest prompts: chat-steady 2048 (qwen25-3b), reason-saturated 1024 (pangu)
CELLS = {name: int(size) for name, size in (pair.split(":") for pair in sys.argv[3:])} or {
    "qwen25-3b": 2048, "pangu-ultra-moe-ep16": 1024}

for name, max_prompt in CELLS.items():
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json")) as f:
        config = json.load(f)
    app = serving_app.build_app(config, serving_app.prompt_buckets(max_prompt), seed=0)
    app.mesh = mesh_from_config(app.tpu_config, devices=topo.devices[:1])
    app._build_wrappers()
    params, cache = app.build_params_struct(), app._cache_struct()
    for tag in ("token_generation_model", "context_encoding_model"):
        for bucket, compiled in app.models[tag].aot_compile(params, cache).items():
            prog = app.models[tag]._programs[bucket]
            text = re.sub(r"__[0-9a-f]{12}_\d+", "__TOKEN", compiled.as_text())  # per-process module token
            text = re.sub(r'source_file="[^"]*"', 'source_file=""', text)
            text = re.sub(r"stack_frame_id=\d+|source_line=\d+", "", text)
            text = "\n".join(ln for ln in text.splitlines() if not FRAME_TABLE.match(ln))
            path = os.path.join(out, f"{name}.{tag}.{bucket}.txt")
            with open(path, "w") as f:
                f.write(text)
            print(os.path.basename(path), len(text.splitlines()), "lines",
                  ",".join(sorted(set(prog.attention_strategies))))
