"""Importing the package initialises no JAX backend.

A chip belongs to one process: a parent that has touched a backend holds
it, and a child that needs it then fails or hangs. Supervisors, launchers
and routers import these modules and then start the processes that do
the work — so the imports themselves must leave
``jax._src.xla_bridge._backends`` empty. Runs in a fresh interpreter (the
test process's own backend has been up since conftest)."""

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import chainermn_tpu
import chainermn_tpu.tracing
import chainermn_tpu.serving.engine
import chainermn_tpu.serving.kv_cache
import chainermn_tpu.fleet.router
import chainermn_tpu.fleet.pools
import chainermn_tpu.training.step
import chainermn_tpu.training.trainer
import chainermn_tpu.ops.fused_ce
import chainermn_tpu.ops.flash_attention
import chainermn_tpu.models.transformer
import chainermn_tpu.resilience.supervisor
import chainermn_tpu.utils
import tools.supervise
import chip_smoke
from jax._src import xla_bridge
assert not xla_bridge._backends, sorted(xla_bridge._backends)
print("no backend")
"""


def test_imports_initialise_no_backend():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO_ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "no backend" in proc.stdout
