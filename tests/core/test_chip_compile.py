"""The engine's main programs compile for a TPU v5e at deployment sizes.

Each test compiles for a described ``v5e:2x2`` topology, which needs the
TPU compiler but no chip: nothing runs, so these say nothing about
results or speed.  They catch what the chip's compiler refuses (a
program that does not fit 16 GB of HBM, a collective it cannot place)
before a chip run does.  The topology is described inside a fixture so
that importing this file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import ControllerConfig, FrontendConfig, compile_spec
from repro.core import device as D
from repro.core import engine as E
from repro.core import frontend as F

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9
HBM3 = ("HBM3", "HBM3_16Gb", "HBM3_5200")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, args, sharding):
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                       sharding=sharding), args)
    return jax.jit(fn).lower(*avals).compile()


def _args(cspec, fp):
    return ((D.dyn_params(cspec),), fp, jnp.uint32(0x1234))


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < V5E_HBM_BYTES, ma
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, ma


def test_hbm3_16ch_scalar_run_compiles_for_one_chip(one_chip,
                                                    no_compile_cache):
    cspec = compile_spec(*HBM3, channels=16)
    fcfg = FrontendConfig()
    fn = E.make_run(cspec, ControllerConfig(), fcfg, 100_000, trace=False)
    _fits_one_chip(_compile(fn, _args(cspec, fcfg.params()), one_chip))


def test_ddr5_4ch_64_point_batch_compiles_for_one_chip(one_chip,
                                                       no_compile_cache):
    cspec = compile_spec("DDR5", "DDR5_16Gb_x8", "DDR5_4800B", channels=4)
    fcfg = FrontendConfig()
    fp = F.stack_params([(1.0 + 0.5 * i, 0.7) for i in range(64)],
                        fcfg.probe_gap)
    fn = jax.vmap(E.make_run(cspec, ControllerConfig(), fcfg, 20_000,
                             trace=False), in_axes=(None, 0, None))
    _fits_one_chip(_compile(fn, _args(cspec, fp), one_chip))


def test_hbm3_16ch_channel_sharded_run_compiles_for_four_chips(
        topo, no_compile_cache, monkeypatch):
    # make_run places the channel mesh on jax.devices(), which here is
    # the CPU: hand it the described chips instead
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    mesh = Mesh(np.asarray(topo.devices[:4]), (E.CHANNEL_AXIS,))
    cspec = compile_spec(*HBM3, channels=16)
    fcfg = FrontendConfig()
    fn = E.make_run(cspec, ControllerConfig(), fcfg, 100_000, trace=False,
                    shard=4)
    compiled = _compile(fn, _args(cspec, fcfg.params()),
                        NamedSharding(mesh, P()))
    _fits_one_chip(compiled)
    # the per-cycle psum/pmin of the sharded loop must cross the chips
    assert "all-reduce" in compiled.as_text()
