"""Each cell's device programs compile for a described TPU v5e.

Nothing runs: a pass says the program lowers, its kernels fit VMEM and
the whole fits HBM, at the sizes the cells serve.  The topology is
described inside a fixture, never at import time.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


C64, F32 = jnp.complex64, jnp.float32


@pytest.fixture(scope="module")
def one_chip(run):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(run, monkeypatch):
    run._program()
    import repro.kernels.dedisp.ops as dedisp
    import repro.kernels.fft.ops as fft
    import repro.kernels.harmonic_sum.ops as hsum
    for mod in (fft, dedisp, hsum):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)


def _compile(fn, sharding, shape, dtype):
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    compiled = jax.jit(fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("mix", ["c2c_4k_1gib", "c2c_64k_1gib"])
def test_batch_mix(run, one_chip, compiled_kernels, mix):
    from repro.fft.plan import plan_for_length
    t = json.loads((run.HERE / "traffic" / f"{mix}.json").read_text())
    _compile(plan_for_length(t["n"]).fn, one_chip,
             (t["requests"] * t["rows"], t["n"]), C64)


def test_pulsar_cell(run, one_chip, compiled_kernels):
    from repro.data.synthetic import FilterbankSpec
    from repro.search.pipeline import (DispersionPlan, pulsar_search,
                                       serving_sifted)
    from repro.search.templates import TemplateBank
    c = run.load_cell("pulsar_htru_1024x128k")
    cfg, fbs = c.config, c.traffic["filterbanks"]
    plan = DispersionPlan.from_spec(
        FilterbankSpec(nchan=cfg["nchan"], ntime=cfg["ntime"]),
        n_trials=cfg["dm_trials"])
    t, h = cfg["templates"], cfg["n_harmonics"]
    bank = TemplateBank.linear(zmax=(t - 1) / 2.0, n_templates=t)
    compiled = _compile(
        lambda x: serving_sifted(pulsar_search(x, plan, bank,
                                               n_harmonics=h)),
        one_chip, (fbs, cfg["nchan"], cfg["ntime"]), F32)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * 2**30
