"""One-off: profile where the multichip dryrun's compile time goes.

Runs dryrun_multichip(8) with the persistent compilation cache switched
off (every program compiles cold) and jax compile logging, printing
per-program compile durations. Evidence for shrinking the gate's compile
surface (VERDICT r04 next-round item 1).
"""

import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge

ge._force_cpu_devices(8)
import jax

jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_log_compiles", True)
logging.basicConfig(level=logging.DEBUG)
for name in ("jax._src.dispatch", "jax._src.interpreters.pxla", "jax._src.compiler"):
    logging.getLogger(name).setLevel(logging.DEBUG)

ge.dryrun_multichip(8)
