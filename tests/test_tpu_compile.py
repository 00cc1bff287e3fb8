"""The flush programs compile for a TPU v5e that is described, not attached.

The XLA compiler for the chip is installed here, so these tests catch what
the chip's compiler would refuse, and programs that do not fit its 16 GB,
without a chip. The arguments are shape structs: the real flush arguments
of a small engine run, captured at dispatch, with the flow-rule table
scaled to the 1,048,576 rules of the north star (building that many
``FlowRule`` objects would cost ~15 s of host time).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

NORTH_STAR_RULES = 1 << 20
HBM_BYTES = 16 * 1024**3  # one v5e chip


class _Captured(Exception):
    pass


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # A described chip's programs can be written to the persistent cache
    # but never read back here: keep it out of the way.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


def _capture(monkeypatch, mesh: bool):
    """Drive a small engine with every rule kind until its first flush
    dispatches; return (function name or sharded kwargs, args, kwargs)."""
    import sentinel_tpu as st
    import sentinel_tpu.parallel as par
    from sentinel_tpu.models import constants as C
    from sentinel_tpu.runtime import engine as E
    from sentinel_tpu.utils.clock import ManualClock

    got = {}

    def recorder(name):
        def record(*args, **kw):
            got.update(name=name, args=args, kw=kw)
            raise _Captured

        return record

    for name in ("flush_step_jit", "flush_step_shaping_jit",
                 "flush_step_param_jit", "flush_step_full_jit"):
        monkeypatch.setattr(E, name, recorder(name))

    def sharded(m, **kw):
        got["sharded_kw"] = kw
        return recorder("sharded")

    monkeypatch.setattr(par, "make_sharded_flush", sharded)
    eng = E.Engine(clock=ManualClock(start_ms=10_000))
    if mesh:
        eng.enable_mesh(4)
    eng.set_flow_rules([
        st.FlowRule("qps", count=5),
        st.FlowRule("rl", count=10, control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                    max_queueing_time_ms=200),
        st.FlowRule("wu", count=40, control_behavior=C.CONTROL_BEHAVIOR_WARM_UP),
    ])
    eng.set_degrade_rules([st.DegradeRule("qps", grade=1, count=0.5, time_window=1)])
    eng.set_param_rules({"api": [st.ParamFlowRule("api", param_idx=0, count=3)]})
    for res in ("qps", "rl", "wu"):
        eng.submit_bulk(res, 1024)
    eng.submit_bulk("api", 1024, args_column=[(f"k{i % 97}",) for i in range(1024)])
    with pytest.raises(_Captured):
        eng.flush()
    return got


def _structs(tree, sharding, n_rules=None):
    """Shape structs of ``tree`` on ``sharding``; with ``n_rules``, every
    leaf with the tree's leading rule dimension is scaled to it."""
    leaves = jax.tree_util.tree_leaves(tree)
    nr = np.shape(leaves[0])[0] if n_rules else None

    def one(x):
        shape = np.shape(x)
        if n_rules and shape and shape[0] == nr:
            shape = (n_rules,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, np.asarray(x).dtype, sharding=sharding)

    return jax.tree_util.tree_map(one, tree)


def _flush_structs(args, where, batch_sharding=None):
    stats, fdev, fdyn, ddev, ddyn, pdyn, sysdev, batch, *extra = args
    return (
        _structs(stats, where),
        _structs(fdev, where, NORTH_STAR_RULES),
        _structs(fdyn, where, NORTH_STAR_RULES),
        _structs(ddev, where),
        _structs(ddyn, where),
        _structs(pdyn, where),
        _structs(sysdev, where),
        batch_sharding(batch) if batch_sharding else _structs(batch, where),
        *(_structs(x, where) for x in extra),
    )


def _fits(compiled) -> None:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES


@pytest.mark.parametrize("variant", ["full", "plain"])
def test_flush_compiles_at_north_star_table(topo, monkeypatch, variant):
    """One v5e chip, 1,048,576-rule flow table: the mixed slot chain
    (shaping + hot-param + breakers) and the plain QPS flush."""
    from sentinel_tpu.runtime import flush as F

    got = _capture(monkeypatch, mesh=False)
    assert got["name"] == "flush_step_full_jit"
    args = _flush_structs(got["args"], SingleDeviceSharding(topo.devices[0]))
    fn = F.flush_step_full_jit
    if variant == "plain":
        fn, args = F.flush_step_jit, args[:8]
    _fits(fn.lower(*args, **got["kw"]).compile())


def test_sharded_flush_compiles_on_four_chips(topo, monkeypatch):
    """The mesh flush over four described chips: replicated tables and
    state, entries split over the chips, and an all-reduce between them."""
    from sentinel_tpu.parallel import batch_partition_specs, make_sharded_flush

    got = _capture(monkeypatch, mesh=True)
    assert got["name"] == "sharded"
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rep = NamedSharding(mesh, PartitionSpec())
    specs = batch_partition_specs("data")

    def split(batch):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                np.shape(x), np.asarray(x).dtype, sharding=NamedSharding(mesh, s)
            ),
            batch, specs,
        )

    fn = make_sharded_flush(mesh, **got["sharded_kw"])
    compiled = fn.lower(*_flush_structs(got["args"], rep, split)).compile()
    _fits(compiled)
    assert "all-reduce" in compiled.as_text()
