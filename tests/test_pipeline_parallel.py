"""GPipe pipeline over a stage axis == sequential execution (subprocess
with 4 host devices)."""

import os
import subprocess
import sys
import textwrap


def test_pipeline_matches_sequential():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline_parallel import pipeline_forward

        P_STAGES, M, MB, D = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (P_STAGES, D, D)) / jnp.sqrt(D)
        xs = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))

        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        from repro.mesh import make_mesh
        mesh = make_mesh((P_STAGES,), ("stage",))
        out = pipeline_forward({"w": ws}, xs, mesh,
                               lambda p, x: stage_fn(p["w"], x))

        ref = xs
        for s in range(P_STAGES):
            ref = jax.vmap(lambda x: stage_fn(ws[s], x))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        from repro.distributed.pipeline_parallel import bubble_fraction
        assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
        print("PIPELINE_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ), timeout=600,
    )
    assert "PIPELINE_OK" in out.stdout, out.stderr[-2000:]
