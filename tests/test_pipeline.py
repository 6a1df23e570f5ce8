"""Pipeline parallelism over a fake multi-device mesh (subprocess — device
count must be set before jax init)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp

    from repro.distributed.pipeline import bubble_fraction, pipeline_apply

    mesh = jax.make_mesh(
        (4, 2), ("pod", "data"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    n_stages, n_micro, mb, d = 4, 8, 2, 16

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (n_stages, d, d)) * 0.5
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))

    out = pipeline_apply(stage_fn, ws, x, mesh, axis="pod")

    # reference: sequential application of all stages per microbatch
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ ws[i])
    err = float(jnp.abs(out - ref).max())
    print(json.dumps({
        "err": err,
        "bubble": bubble_fraction(n_stages, n_micro),
        "shape_ok": out.shape == ref.shape,
    }))
    """
)


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_pipeline_matches_sequential(result):
    assert result["shape_ok"]
    assert result["err"] < 1e-5


def test_bubble_fraction(result):
    assert result["bubble"] == pytest.approx(3 / 11)
