"""Launch-layer integration: step builders lower + compile on a small mesh
(subprocess with 4 host devices) — a miniature of the production dry-run."""

import os
import subprocess
import sys
import textwrap


def _run(code: str):
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ), timeout=900,
    )
    assert "STEPS_OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])


def test_train_step_lowers_on_small_mesh():
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.configs import get_config
        from repro.launch import steps
        from repro.mesh import make_mesh
        from repro.models import build_model
        from repro.optim import AdamW

        cfg = get_config("h2o-danube-1.8b", smoke=True)
        mesh = make_mesh((2, 2), ("data", "model"))
        rules = steps.resolve_rules(cfg, mesh)
        with mesh:
            jitted, abstract = steps.jit_train_step(
                build_model(cfg), AdamW(), mesh, rules,
                microbatches=2, batch=4, seq=32,
            )
            compiled = jitted.lower(*abstract).compile()
        assert compiled.cost_analysis() is not None
        print("STEPS_OK")
    """))


def test_decode_step_lowers_on_small_mesh():
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.configs import get_config
        from repro.launch import steps
        from repro.mesh import make_mesh
        from repro.models import build_model

        cfg = get_config("gemma3-1b", smoke=True)
        mesh = make_mesh((2, 2), ("data", "model"))
        rules = steps.resolve_rules(
            cfg, mesh, overrides={"cache_seq": "model",
                                  "act_cache_seq": "model"})
        with mesh:
            jitted, abstract = steps.jit_decode_step(
                build_model(cfg), mesh, rules, batch=4, seq=64,
            )
            compiled = jitted.lower(*abstract).compile()
        hlo = compiled.as_text()
        assert "dynamic-update-slice" in hlo  # cache update survived
        print("STEPS_OK")
    """))
