"""Fast-tier multi-process check: a real ``jax.distributed.initialize`` runs
in the DEFAULT suite.

Round 1 gated every multi-process test behind RUN_SLOW, so the default suite
(and the round's record) never exercised the distributed bootstrap at all.
This is the minimal always-on version: two OS processes join a coordination
group via ``cluster.bootstrap`` (the reference's localhost-ports cluster
simulation, reference README.md:27-31) and run one sync-DP step over the
combined mesh. The fuller smoke (scanned epoch, async exchange, compiled
run, fault injection) stays in tests/integration/ behind RUN_SLOW.
"""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.config import ClusterConfig
from distributed_tensorflow_tpu.models import MLP
from distributed_tensorflow_tpu.ops import cross_entropy, sgd
from distributed_tensorflow_tpu.parallel import SyncDataParallel, make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

task = int(sys.argv[1])
cluster = ClusterConfig.from_lists(["127.0.0.1:29781", "127.0.0.1:29782"])
ctx = bootstrap(cluster, "worker", task)
assert jax.process_count() == 2, jax.process_count()

mesh = make_mesh()
model = MLP(hidden_dim=16, compute_dtype=jax.numpy.float32)
strat = SyncDataParallel(mesh)
state = strat.init_state(model, sgd(0.001), seed=1)
step = strat.make_train_step(model, cross_entropy, sgd(0.001))
rng = np.random.default_rng(0)
n = mesh.shape["data"] * 2
sharding = NamedSharding(mesh, P("data"))
x = jax.make_array_from_process_local_data(
    sharding, rng.random((n // 2, 784), dtype=np.float32), (n, 784))
y = jax.make_array_from_process_local_data(
    sharding, np.eye(10, dtype=np.float32)[rng.integers(0, 10, n // 2)], (n, 10))
state, cost = step(state, x, y)
cost = float(jax.device_get(cost))
assert np.isfinite(cost), cost

# One LM dp step over the same 2-process mesh (models/gpt.py): token batch
# sharded across processes, grads all-reduced over DCN.
import jax.numpy as jnp
from distributed_tensorflow_tpu.models.gpt import GPTLM, make_lm_train_step
from distributed_tensorflow_tpu.ops import optim as optim_lib

lm = GPTLM(vocab_size=32, max_len=16, model_dim=16, num_heads=2,
           num_layers=1, compute_dtype=jnp.float32)
lp = lm.init(seed=1)
lopt = optim_lib.make("adam", 1e-3)
lstep = make_lm_train_step(lm, lopt, mesh=mesh)
toks = jax.make_array_from_process_local_data(
    sharding, rng.integers(0, 32, size=(2, 16)).astype(np.int32), (4, 16))
lp, _, lm_loss = lstep(lp, lopt.init(lp), toks)
lm_loss = float(jax.device_get(lm_loss))
assert np.isfinite(lm_loss), lm_loss
print("FASTMP_OK", task, cost, lm_loss)
"""


def test_two_process_bootstrap_and_sync_step():
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + _REPO
    # One device per process: keeps compile tiny and the check ~10s.
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(i)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for i, out in enumerate(outs):
        assert procs[i].returncode == 0, f"task {i} failed:\n{out}"
        assert f"FASTMP_OK {i}" in out, out
