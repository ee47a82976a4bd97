"""Multi-device integration tests.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count
so the main pytest process keeps its single default device (per the
dry-run isolation contract in the launch package).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + env.get("XLA_FLAGS", "")
    )
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_pencil_fft_matches_reference():
    """Distributed four-step FFT over 8 devices == jnp.fft.fft."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.fft.distributed import pencil_fft, untranspose_ref

        mesh = jax.make_mesh((8,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        n1, n2, batch = 64, 128, 2
        key = jax.random.PRNGKey(0)
        x = (jax.random.normal(key, (batch, n1, n2)) +
             1j * jax.random.normal(jax.random.PRNGKey(1), (batch, n1, n2))
             ).astype(jnp.complex64)
        xs = jax.device_put(x, NamedSharding(mesh, P(None, "model", None)))
        y = pencil_fft(xs, mesh, n1=n1, n2=n2)
        got = untranspose_ref(jax.device_get(y), n1, n2)
        want = np.fft.fft(np.asarray(x).reshape(batch, n1 * n2), axis=-1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
        print("pencil ok")
    """)


@pytest.mark.slow
@pytest.mark.parametrize("axis_type", ["Auto", "Explicit"])
def test_batch_parallel_fft(axis_type):
    """A divided batch stays sharded; a padded one (13 rows on 8 devices)
    is un-padded correctly on both kinds of mesh axis."""
    run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.fft.distributed import batch_parallel_fft

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(AxisType.{axis_type},))
        for rows in (16, 13):
            x = (jax.random.normal(jax.random.PRNGKey(0), (rows, 512)) +
                 1j * jax.random.normal(jax.random.PRNGKey(1), (rows, 512))
                 ).astype(jnp.complex64)
            xs = jax.device_put(x, NamedSharding(mesh, P()))
            y = batch_parallel_fft(xs, mesh)
            assert y.shape == (rows, 512), y.shape
            if rows == 16:
                assert y.sharding.spec[0] == "data", y.sharding
            np.testing.assert_allclose(jax.device_get(y),
                                       np.fft.fft(np.asarray(x), axis=-1),
                                       rtol=2e-3, atol=2e-3)
        print("batch ok")
    """)


@pytest.mark.slow
def test_pencil_rfft_matches_reference():
    """Distributed R2C pencil (packed + sharded Hermitian split) == rfft."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.fft.distributed import assemble_rfft_pencil, pencil_fft

        mesh = jax.make_mesh((8,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        n1, n2, batch = 32, 64, 2
        x = jax.random.normal(jax.random.PRNGKey(0), (batch, n1, n2),
                              jnp.float32)
        xs = jax.device_put(x, NamedSharding(mesh, P(None, "model", None)))
        y = pencil_fft(xs, mesh, n1=n1, n2=n2, kind="r2c")
        got = assemble_rfft_pencil(jax.device_get(y), n1, n2)
        want = np.fft.rfft(np.asarray(x).reshape(batch, n1 * n2), axis=-1)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
        print("pencil r2c ok")
    """)


@pytest.mark.slow
@pytest.mark.parametrize("axis_type", ["Auto", "Explicit"])
def test_batch_parallel_fft_r2c_kind(axis_type):
    """kind="r2c" shards real batches through the R2C plan (no complex
    cast) and matches jnp.fft.rfft, divided or padded (13 rows)."""
    run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.fft.distributed import batch_parallel_fft

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(AxisType.{axis_type},))
        for rows in (16, 13):
            x = jax.random.normal(jax.random.PRNGKey(0), (rows, 512),
                                  jnp.float32)
            xs = jax.device_put(x, NamedSharding(mesh, P()))
            y = batch_parallel_fft(xs, mesh, kind="r2c")
            assert y.shape == (rows, 257), y.shape
            np.testing.assert_allclose(jax.device_get(y),
                                       np.fft.rfft(np.asarray(x), axis=-1),
                                       rtol=2e-3, atol=2e-3)
        print("batch r2c ok")
    """)


@pytest.mark.slow
def test_batch_parallel_fft_2d_plan_graph():
    """Rank-3 payloads shard over the batch and run the N-D plan graph."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.fft.distributed import batch_parallel_fft

        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = (jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32)) +
             1j * jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
             ).astype(jnp.complex64)
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y = batch_parallel_fft(xs, mesh)
        np.testing.assert_allclose(jax.device_get(y),
                                   np.fft.fft2(np.asarray(x), axes=(-2, -1)),
                                   rtol=2e-3, atol=2e-3)
        print("batch 2d ok")
    """, n_devices=4)


@pytest.mark.slow
def test_pencil_collective_bytes_formula():
    """The analytic all_to_all byte count matches the sharded layout."""
    from repro.fft.distributed import pencil_collective_bytes
    b = pencil_collective_bytes(batch=2, n1=64, n2=128, n_devices=8)
    local = 2 * 64 * 128 / 8 * 8
    assert b == pytest.approx(2 * local * 7 / 8)
    # R2C: two all_to_alls on the packed half-length transform plus the
    # mirror ppermute — strictly cheaper than the complex path.
    r = pencil_collective_bytes(batch=2, n1=64, n2=128, n_devices=8,
                                kind="r2c")
    assert r == pytest.approx(3 * (local / 2) * 7 / 8)
    assert r < b
