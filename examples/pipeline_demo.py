"""Pipeline-parallelism demo: 4 stages over 4 (host) devices, GPipe
schedule via shard_map + ppermute.

  PYTHONPATH=src python examples/pipeline_demo.py
(sets XLA_FLAGS itself — run as a standalone script)
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.pipeline_parallel import bubble_fraction, pipeline_forward

P_STAGES, M, MB, D = 4, 8, 4, 64
ws = jax.random.normal(jax.random.PRNGKey(0), (P_STAGES, D, D)) / jnp.sqrt(D)
xs = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

from repro.mesh import make_mesh

mesh = make_mesh((P_STAGES,), ("stage",))
out = pipeline_forward(
    {"w": ws}, xs, mesh, lambda p, x: jnp.tanh(x @ p["w"])
)

ref = xs
for s in range(P_STAGES):
    ref = jax.vmap(lambda x: jnp.tanh(x @ ws[s]))(ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print(f"pipeline over {P_STAGES} stages x {M} microbatches: outputs match "
      f"sequential execution")
print(f"bubble fraction: {bubble_fraction(P_STAGES, M):.3f} "
      f"(GPipe (P-1)/(P+M-1))")
