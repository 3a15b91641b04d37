"""Sharding rules + models under a real (4-device) mesh.

These tests re-exec a small script with XLA_FLAGS so they get multiple
host devices without polluting the main test process.
"""
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    RULES_V0, RULES_V2, RULES_V3, logical_to_spec, sanitize_spec)


def test_logical_to_spec_basic():
    assert logical_to_spec(("embed", "mlp"), RULES_V2) == \
        P("data", "model")
    assert logical_to_spec(("batch", "seq", None), RULES_V2) == \
        P(("pod", "data"), "model")


def test_duplicate_mesh_axis_dropped():
    # seq and heads both map to model in v2; second use must drop
    spec = logical_to_spec(("seq", "heads"), RULES_V2)
    assert spec == P("model")


def test_v0_has_no_tensor_parallelism():
    assert logical_to_spec(("embed", "mlp"), RULES_V0) == P("data")


def test_v3_conflicts_by_design():
    # v3: attention on model, ffn on data — the paper's regression case
    assert logical_to_spec(("embed", "mlp"), RULES_V3) == P(None, "data")
    assert logical_to_spec((None, "heads"), RULES_V3) == P(None, "model")


def test_sanitize_spec_drops_nondividing():
    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)
    spec = sanitize_spec((50280, 2560), P("model", "data"), FakeMesh)
    assert spec == P(None, "data")   # 50280 % 16 != 0, 2560 % 16 == 0


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, reduced
    from repro.distributed import context as dctx
    from repro.distributed.sharding import rules_for, tree_shardings
    from repro.models import Model
    from repro.models.params import param_pspecs

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    rules = rules_for("v2")
    cfg = reduced(get_config("{arch}"), num_kv_heads=2)
    model = Model(cfg)

    with dctx.use_mesh(mesh), dctx.use_rules(rules):
        params = model.init(jax.random.PRNGKey(0))
        specs = model.param_specs()
        shardings = tree_shardings(model.abstract_params(),
                                   param_pspecs(specs, rules, mesh), mesh)
        params = jax.device_put(params, shardings)
        B, S = 4, 16
        batch = {{"tokens": jnp.zeros((B, S), jnp.int32)}}
        logits, aux = jax.jit(model.forward)(params, batch)
        # distributed == single-device result
        params_local = jax.device_put(
            params, jax.devices()[0])
        with dctx.use_mesh(None):
            ref, _ = jax.jit(model.forward)(params_local, batch)
        a = np.asarray(logits, np.float32)
        b = np.asarray(ref, np.float32)
        err = np.abs(a - b).max() / (np.abs(b).max() + 1e-6)
        assert err < 2e-2, err
        print("SHARDED_OK", err)
""")


@pytest.mark.parametrize("arch", ["deepseek-7b", "phi3.5-moe-42b-a6.6b"])
def test_sharded_forward_matches_single_device(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT.format(arch=arch)],
        capture_output=True, text=True, env=env, timeout=600)
    assert "SHARDED_OK" in proc.stdout, proc.stderr[-2000:]
