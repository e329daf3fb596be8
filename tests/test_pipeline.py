"""GPipe pipeline-over-pods: correctness + differentiability.

Needs >1 device for a real pipeline, so the multi-stage cases run in a
subprocess with forced host devices (the in-process test suite must keep
the single-CPU device count — see conftest)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import auto_mesh
from repro.parallel.pipeline import pipeline_apply

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import auto_mesh
    from repro.parallel.pipeline import pipeline_apply

    P, M, D = 4, 8, 16
    mesh = auto_mesh((P,), ("pod",))
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.standard_normal((P, D, D)) / D**0.5, jnp.float32)
    x = jnp.asarray(rng.standard_normal((M, 3, D)), jnp.float32)

    def stage(w, h):
        return jnp.tanh(h @ w)

    out = pipeline_apply(mesh, stage, ws, x, pod_axis="pod")

    ref = x
    for s in range(P):
        ref = jnp.tanh(ref @ ws[s])
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, ("forward", err)

    # differentiability: grads of the pipelined loss match sequential
    def loss_pipe(ws_):
        return jnp.sum(pipeline_apply(mesh, stage, ws_, x, pod_axis="pod") ** 2)

    def loss_seq(ws_):
        h = x
        for s in range(P):
            h = jnp.tanh(h @ ws_[s])
        return jnp.sum(h ** 2)

    g1 = jax.grad(loss_pipe)(ws)
    g2 = jax.grad(loss_seq)(ws)
    gerr = float(jnp.abs(g1 - g2).max() / jnp.abs(g2).max())
    assert gerr < 1e-4, ("grad", gerr)
    print("PIPELINE-OK", err, gerr)
""")


def test_gpipe_multistage_subprocess():
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "PIPELINE-OK" in res.stdout, res.stdout + res.stderr


def test_gpipe_single_stage_degenerate():
    """P=1 pipeline == plain application (runs on the real single device)."""
    mesh = auto_mesh((1,), ("pod",))
    w = jnp.ones((1, 4, 4)) * 0.1
    x = jnp.ones((3, 2, 4))
    out = pipeline_apply(mesh, lambda w_, h: h @ w_, w, x, pod_axis="pod")
    ref = x @ w[0]
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
