"""``benchmark/run.py`` of the checkout in the working directory, which then
prints, on standard error, the expert form each step program took: every
series of the registry's ``nxdi_moe_expert_form_programs_total{submodel, form}``
and each program's ``expert_forms``, as lines ``expert form ...``. Arguments
are ``run.py``'s; its exit code is kept.

    cd <checkout> && python3 <this file> --workload <cell> --seed <n> --seconds 51 --trace 1
"""
import os
import runpy
import sys

sys.path.insert(0, os.getcwd())
from benchmark import serving_app  # noqa: E402

apps = []
build = serving_app.build_app


def kept(*args, **kwargs):
    app = build(*args, **kwargs)
    apps.append(app)
    return app


serving_app.build_app = kept
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
code = 0
try:
    runpy.run_path("benchmark/run.py", run_name="__main__")
except SystemExit as e:
    code = e.code
for app in apps:
    counted = app.telemetry.expert_form_programs
    for key in counted.series():
        print(f"expert form counted {counted.labels_of(key)}", file=sys.stderr)
    for tag, wrapper in app.models.items():
        for prog in wrapper._programs.values():
            print(f"expert form {prog.label}: {prog.expert_forms}", file=sys.stderr)
sys.exit(code)
