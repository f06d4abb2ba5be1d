"""``benchmark/run.py`` of the checkout in the working directory, with the
program's own INFO log on standard error (the harness configures no logging):
the load-time layout line ``params: ... laid out ...`` and each program's
attention strategies. Arguments are ``run.py``'s.

    cd <checkout> && python3 <this file> --workload <cell> --seed <n> --seconds 51 --trace 1
"""
import logging
import os
import runpy
import sys

logging.basicConfig(level=logging.WARNING, format="%(name)s: %(message)s")
logging.getLogger("nxdi_tpu").setLevel(logging.INFO)
sys.path.insert(0, os.getcwd())
sys.argv = ["benchmark/run.py"] + sys.argv[1:]
runpy.run_path("benchmark/run.py", run_name="__main__")
