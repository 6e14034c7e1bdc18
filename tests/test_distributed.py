"""Multi-device tests (subprocess: needs forced host device count).

These exercise the paper's distribution scheme: pyramid branch exchange
(psum exactness) and the sharded simulation loop — on 8 fake CPU devices.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import octree
from repro.core.distributed import DistributedPlasticityEngine
from repro.core.engine import EngineConfig, PlasticityEngine
from repro.core.msp import MSPConfig
from repro.core.traversal import FMMConfig

assert len(jax.devices()) == 8

rng = np.random.default_rng(0)
n = 256
pos = rng.uniform(0, 1000.0, (n, 3)).astype(np.float32)
msp_cfg = MSPConfig.calibrated(speedup=100.0)
fmm_cfg = FMMConfig(c1=8, c2=8)

# --- 1. pyramid branch-exchange exactness (box-ownership partials) -------
mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
deng = DistributedPlasticityEngine(pos, mesh, "data", msp_cfg, fmm_cfg,
                                   EngineConfig(method="fmm"))
# single-device pyramid on the SAME (morton-sorted) positions
seng = PlasticityEngine(deng.positions_np, msp_cfg, fmm_cfg,
                        EngineConfig(method="fmm"))
ax = jnp.array(rng.integers(0, 3, n), jnp.float32)
den = jnp.array(rng.integers(0, 3, n), jnp.float32)
# jit the reference: the parity contract relates COMPILED programs (the
# engines always run jitted); eager op-by-op dispatch may round fused
# elementwise chains differently, which is not a shard-count effect.
ref_levels = jax.jit(lambda a, d: octree.build_pyramid(
    seng.structure, seng.positions, a, d, fmm_cfg.delta))(ax, den)

from jax.sharding import PartitionSpec as P
got_levels = jax.jit(jax.shard_map(
    lambda a, d: deng._local_pyramid(a, d), mesh=mesh,
    in_specs=(P(), P()), out_specs=P(), check_vma=False))(ax, den)
# each box is aggregated wholly by its owner device, so the psum merge is
# BITWISE equal to the single-device build
for l, (a, b) in enumerate(zip(ref_levels, got_levels)):
    for name in ("den_w", "ax_w", "den_c", "ax_c", "herm", "moms"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=f"level {l} {name}")
print("PYRAMID_OK")

# --- 2. sharded simulation == single-device simulation, bitwise ----------
st, recs = deng.simulate(deng.init_state(), jax.random.key(0), 1500)
_, ref = seng.simulate(seng.init_state(), jax.random.key(0), 1500)
for name in ("num_synapses", "calcium_mean", "calcium_std", "spike_rate"):
    np.testing.assert_array_equal(np.asarray(getattr(recs, name)),
                                  np.asarray(getattr(ref, name)), err_msg=name)
ca = float(np.asarray(recs.calcium_mean)[-1])
syn = int(np.asarray(recs.num_synapses)[-1])
assert np.isfinite(ca) and ca > 0.1, ca
assert syn > 50, syn
print("SIM_OK", ca, syn)
'''


@pytest.mark.slow
def test_distributed_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "PYRAMID_OK" in res.stdout
    assert "SIM_OK" in res.stdout
