"""Multi-host (DCN) bring-up smoke test: two REAL processes form a
jax.distributed cluster through the multiproc launcher and run a psum
across hosts.

Mirrors the reference's single-node multi-process strategy
(MultiProcessTestCase spawning NCCL workers, distributed_test_base.py:22-74)
— here each spawned process is one 'host' with one CPU device, launched
via apex_tpu.parallel.multiproc (the env hand-off path a scheduler would
use), and the cross-host collective rides the jax.distributed (DCN-analog)
backend.
"""

import os
import socket
import subprocess
import sys

import pytest

WORKER = r"""
import os, sys
import jax
sys.path.insert(0, os.environ["APEX_TPU_REPO"])
from apex_tpu.parallel.multiproc import initialize_distributed
initialize_distributed()  # reads APEX_TPU_* env set by the launcher
assert jax.process_count() == 2, jax.process_count()
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
import numpy as np
mesh = Mesh(np.asarray(jax.devices()), ("dp",))
@jax.jit
def allreduce(x):
    return jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                         in_specs=P("dp"), out_specs=P())(x)
import jax.experimental.multihost_utils as mh
local = jnp.full((1, 4), float(jax.process_index() + 1))
x = mh.host_local_array_to_global_array(local, mesh, P("dp"))
out = allreduce(x)
got = np.asarray(mh.global_array_to_host_local_array(out, mesh, P()))
np.testing.assert_allclose(got, 3.0)  # 1 + 2 across the two hosts
print(f"RANK{jax.process_index()}_OK")
"""


@pytest.mark.slow
def test_two_process_cluster_psum(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env.update({"JAX_PLATFORMS": "cpu", "APEX_TPU_REPO": repo,
                "JAX_NUM_CPU_DEVICES": "1"})
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "apex_tpu.parallel.multiproc",
             "--nnodes", "2", "--node_rank", str(r),
             "--coordinator", f"127.0.0.1:{port}", str(script)],
            cwd=repo, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for r in range(2)
    ]
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
    assert "RANK0_OK" in outs[0] + outs[1]
    assert "RANK1_OK" in outs[0] + outs[1]
