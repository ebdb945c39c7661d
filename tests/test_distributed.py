"""Multi-device (8 fake CPU devices) pjit integration: the production code
path — sharded params, gradient accumulation, batch sharding — executes
(not just lowers) on a (2, 4) data x model mesh. Runs in a subprocess so
the device-count flag doesn't leak into other tests."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import OptimConfig, get_reduced
    from repro.distributed.sharding import batch_spec, param_specs
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step, make_train_state
    from repro.models.api import ModelSpec

    assert jax.device_count() == 8
    mesh = make_mesh((2, 4), ("data", "model"))
    spec = ModelSpec(get_reduced("qwen3-1.7b"))
    schema = spec.schema()
    with jax.set_mesh(mesh):
        psp = param_specs(schema, mesh)
        p_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), psp)
        state = make_train_state(spec, jax.random.PRNGKey(0))
        state = {
            "params": jax.device_put(state["params"], p_sh),
            "opt": type(state["opt"])(
                jax.device_put(state["opt"].step, NamedSharding(mesh, P())),
                jax.device_put(state["opt"].mu, p_sh),
                jax.device_put(state["opt"].nu, p_sh),
                jax.device_put(state["opt"].master, p_sh),
            ),
        }
        step = jax.jit(build_train_step(spec, OptimConfig(lr=1e-3), accum_steps=2),
                       donate_argnums=0)
        batch = {"tokens": jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 100, jnp.int32),
            NamedSharding(mesh, batch_spec(mesh)))}
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(l == l for l in losses), losses  # finite
        assert losses[2] < losses[0], losses  # memorizing one batch
        print("DISTRIBUTED-OK", losses)
    """
)


def test_multidevice_train_step():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=420,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DISTRIBUTED-OK" in r.stdout
