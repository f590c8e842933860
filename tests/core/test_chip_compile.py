"""The engine's main programs compile for a TPU v5e at deployment sizes.

Each test compiles for a described ``v5e:2x2`` topology, which needs the
TPU compiler but no chip: nothing runs, so these say nothing about
results or speed.  They catch what the chip's compiler refuses (a
program that does not fit 16 GB of HBM, a collective it cannot place)
before a chip run does, and a gather left in the cycle body's controller
or horizon (XLA:TPU runs one under the engine's vmap nesting as a
near-serial loop over its elements).  The topology is described inside a
fixture so that importing this file never loads the TPU library; the
jaxpr twin of the gather check needs no TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import ControllerConfig, FrontendConfig, compile_spec
from repro.core import controller as C
from repro.core import device as D
from repro.core import engine as E
from repro.core import frontend as F

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9
HBM3 = ("HBM3", "HBM3_16Gb", "HBM3_5200")
DDR5_2R = ("DDR5", "DDR5_16Gb_x8_2R", "DDR5_4800B")
#: one standard of each controller path: dual command bus, split
#: activation, data-clock sync
GATHER_FREE = {"DDR5_2R": DDR5_2R, "HBM3": HBM3,
               "LPDDR5_2R": ("LPDDR5", "LPDDR5_8Gb_x16_2R", "LPDDR5_6400"),
               "GDDR7": ("GDDR7", "GDDR7_16Gb_x32", "GDDR7_32")}


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


def _body_gathers(hlo_text):
    """``(op_name, shape)`` of every gather whose op name lies in the loop
    body's ``controller`` or ``horizon`` scope."""
    out = []
    for line in hlo_text.splitlines():
        m = re.search(r"= (\S+) gather\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name and re.search(r"while/body/(controller|horizon)/",
                                    name.group(1)):
            out.append((name.group(1), m.group(1)))
    return out


def test_ddr5_8ch_sweep_body_has_no_gather(one_chip, no_compile_cache):
    """The 24-point DDR5 8-channel 2R sweep program (the benchmark's
    ``sweep24`` shapes) keeps no gather in its controller or horizon;
    BlockHammer and PRAC, the only lookups left by index, are off."""
    cspec = compile_spec(*DDR5_2R, channels=8)
    fcfg = FrontendConfig()
    fp = F.stack_params([(1.0 + 0.5 * i, 0.7) for i in range(24)],
                        fcfg.probe_gap)
    fn = jax.vmap(E.make_run(cspec, ControllerConfig(), fcfg, 50_000,
                             trace=False), in_axes=(None, 0, None))
    text = _compile(fn, _args(cspec, fp), one_chip).as_text()
    assert "while/body/controller/" in text      # the scopes are named
    assert _body_gathers(text) == []


def _count_prims(jaxpr, name):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for p in eqn.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(q, "jaxpr", q)
                if hasattr(inner, "eqns"):
                    n += _count_prims(inner, name)
    return n


def _idle_horizon(cspec, dp, ccfg, cs, clk):
    """``idle_horizon`` fed with a lookup of the pass's shapes."""
    q = jnp.zeros(cs.queue.valid.shape, jnp.int32) + clk
    ru = jnp.zeros((cspec.n_refresh_units,), jnp.int32) + clk
    return C.idle_horizon(cspec, dp, ccfg, cs, clk, q, ru)


PARTS = {"controller_step": C.controller_step,
         "channel_horizon": C.channel_horizon,
         "idle_horizon": _idle_horizon}


@pytest.mark.parametrize("part", sorted(PARTS))
@pytest.mark.parametrize("std", sorted(GATHER_FREE))
def test_controller_and_horizon_jaxpr_have_no_gather(std, part):
    """The jaxpr twin of the described-chip check, for the tier-1 run
    where no TPU compiler is installed: ``controller_step``,
    ``channel_horizon`` and ``idle_horizon`` vmapped over 4 channels
    trace no gather."""
    cspec = compile_spec(*GATHER_FREE[std], channels=4)
    ccfg = ControllerConfig()
    dp = D.dyn_params(cspec)
    cs = jax.vmap(lambda _: C.init_ctrl_state(cspec, ccfg.queue_depth))(
        jnp.arange(4))
    fn = PARTS[part]
    jx = jax.make_jaxpr(jax.vmap(
        lambda s: fn(cspec, dp, ccfg, s, jnp.int32(100))))(cs)
    assert _count_prims(jx.jaxpr, "gather") == 0


#: the per-slot lookup's two parts, named in a test-only scope each
LOOKUP_PARTS = ("table_at", "earliest_ready_table")


def _lookup_scopes(monkeypatch, triple) -> dict:
    """``{loop part: lookup parts under it}`` in the op names of a
    2-point vmapped fast-forward program of a 2-channel ``triple``,
    compiled on the host, with ``D.table_at`` and
    ``D.earliest_ready_table`` each run under a scope of its name."""
    for name in LOOKUP_PARTS:
        fn = getattr(D, name)

        def scoped(*a, _fn=fn, _name=name, **k):
            with jax.named_scope(_name):
                return _fn(*a, **k)
        monkeypatch.setattr(D, name, scoped)
    cspec = compile_spec(*triple, channels=2)
    fcfg = FrontendConfig()
    fp = F.stack_params([(16.0, 0.7), (1.0, 0.7)], fcfg.probe_gap)
    fn = jax.vmap(E.make_run(cspec, ControllerConfig(), fcfg, 300,
                             trace=False), in_axes=(None, 0, None))
    text = jax.jit(fn).lower(*_args(cspec, fp)).compile().as_text()
    found = {}
    for path in re.findall(r'op_name="([^"]*)"', text):
        # a scope entered under vmap reads "vmap(<scope>)"
        parts = [re.sub(r"^vmap\((.*)\)$", r"\1", p)
                 for p in path.split("/")]
        loop = [p for p in parts if p in ("controller", "horizon")]
        if "body" in parts and loop:
            found.setdefault(loop[0], set()).update(
                set(parts) & set(LOOKUP_PARTS))
    return found


@pytest.mark.parametrize("triple,horizon", [
    (DDR5_2R, set()),
    (("LPDDR5", "LPDDR5_8Gb_x16_2R", "LPDDR5_6400"), set(LOOKUP_PARTS)),
])
def test_horizon_reads_the_controllers_lookup_unless_the_clock_decides(
        monkeypatch, triple, horizon):
    """DDR5's fast-forward horizon builds no earliest-ready table and
    reads none per slot: it reduces the controller's lookup.  LPDDR5,
    whose prerequisite decode reads the clock, looks up anew there."""
    found = _lookup_scopes(monkeypatch, triple)
    assert found["controller"] == set(LOOKUP_PARTS)
    assert found["horizon"] == horizon
