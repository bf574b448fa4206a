"""Ahead-of-time compiles of the main path for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``): these tests
catch what the chip's compiler would refuse (memory, layout,
partitioning) at no chip time. Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file. All such compiles stay in this one file.
"""

import os
import types

import jax
import numpy as np
import pytest
from jax import enable_x64
from jax.sharding import Mesh, SingleDeviceSharding

from poseidon_tpu.graph.builder import FlowGraphBuilder
from poseidon_tpu.graph.network import pad_bucket
from poseidon_tpu.models import get_cost_model
from poseidon_tpu.models.costs import build_cost_inputs_host
from poseidon_tpu.ops import resident as res
from poseidon_tpu.ops.dense_auction import _solve, build_dense_instance, default_fuse
from poseidon_tpu.ops.transport import extract_instance, extract_topology
from poseidon_tpu.synth import config2_quincy_flagship, make_synthetic_cluster

ALPHA = 1024
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip are written to a persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """Host pytree -> ShapeDtypeStructs as ``device_put`` would upload
    them (x64 off: 64-bit leaves land as 32-bit)."""
    def leaf(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        dt = jax.dtypes.canonicalize_dtype(x.dtype)
        return jax.ShapeDtypeStruct(x.shape, dt, sharding=sharding)

    return jax.tree_util.tree_map(leaf, tree)


@pytest.fixture(scope="module")
def flagship():
    """The flagship round's host inputs, padded as
    ``ResidentSolver.begin_round`` pads a cold round (1,000 nodes,
    10,000 pending pods, Quincy)."""
    arrays, meta = FlowGraphBuilder().build_arrays(config2_quincy_flagship(seed=0))
    topo = extract_topology(meta, arrays["src"], arrays["dst"], arrays["cap"])
    inputs = build_cost_inputs_host(
        pad_bucket(meta.n_arcs), meta,
        t_min=pad_bucket(len(meta.task_uids), minimum=1),
        m_min=pad_bucket(len(meta.machine_names), minimum=1),
    )
    dt = res.pad_topology(topo, p_min=topo.max_prefs)
    Tp, Mp = dt.arc_unsched.shape[0], dt.slots.shape[0]
    return types.SimpleNamespace(
        meta=meta, inputs=inputs, dt=dt, Tp=Tp, Mp=Mp, P=topo.max_prefs,
        smax=min(pad_bucket(int(topo.slots.max(initial=1)), minimum=1), Tp),
        model_fn=get_cost_model("quincy"),
    )


def _resident_args(f, sharding):
    return (_shapes(f.dt, sharding), _shapes(f.inputs, sharding))


def _resident_kw(f):
    return dict(
        model_fn=f.model_fn, n_prefs=f.P, smax=f.smax, alpha=ALPHA,
        max_rounds=default_fuse(),
    )


def test_resident_round_compiles_at_flagship_width(flagship, one_chip):
    f = flagship
    assert (f.Tp, f.Mp) == (10240, 1024)
    with enable_x64(True):
        compiled = res._resident_chain.lower(
            *_resident_args(f, one_chip), **_resident_kw(f)
        ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES


def test_express_step_compiles_at_flagship_width(flagship, one_chip):
    f = flagship
    kmax = 16  # deploy/poseidon-tpu.cfg --express_max_batch
    with enable_x64(True):
        out = jax.eval_shape(
            lambda *a: res._resident_chain(*a, **_resident_kw(f)),
            *_resident_args(f, one_chip),
        )
    asg, lvl, floor, cost, dev = out[0], out[1], out[2], out[12], out[13]
    ctx = types.SimpleNamespace(meta=f.meta, machine_kwargs={})
    mini = res.ResidentSolver._express_mini_inputs(None, ctx, [], kmax, f.P)
    rows = jax.ShapeDtypeStruct((kmax,), np.int32, sharding=one_chip)
    pm = jax.ShapeDtypeStruct((kmax, f.P), np.int32, sharding=one_chip)
    place = lambda t: _shapes(t, one_chip)  # noqa: E731
    with enable_x64(True):
        compiled = res._express_chain.lower(
            place(dev), _shapes(f.dt, one_chip), place(cost), place(mini),
            place(asg), place(lvl), place(floor), rows, pm, pm,
            model_fn=f.model_fn, kmax=kmax, pk=f.P, alpha=ALPHA,
            max_rounds=res.EXPRESS_FUSE, smax=f.smax, change_cap=256,
        ).compile()
    assert compiled.as_text()


@pytest.fixture(scope="module")
def small_dense():
    """A 1024-task x 128-machine dense instance (host-built)."""
    from poseidon_tpu.models import build_cost_inputs

    cluster = make_synthetic_cluster(128, 1024, seed=3, prefs_per_task=2)
    net, meta = FlowGraphBuilder().build(cluster)
    pending = cluster.pending()
    inputs = build_cost_inputs(
        net, meta,
        task_cpu_milli=np.array([int(t.cpu_request * 1000) for t in pending]),
        task_mem_kb=np.array([t.memory_request_kb for t in pending]),
    )
    inst = extract_instance(net.with_costs(get_cost_model("quincy")(inputs)), meta)
    dev = build_dense_instance(inst)
    assert dev.c.shape == (1024, 128)
    return dev


def _compile_solve(dev):
    Tp, Mp = dev.c.shape
    sh = dev.u.sharding  # the task-axis layout of the instance
    mach = dev.s.sharding
    with enable_x64(True):
        return _solve.lower(
            dev,
            jax.ShapeDtypeStruct((Tp,), np.int32, sharding=sh),
            jax.ShapeDtypeStruct((Tp,), np.int32, sharding=sh),
            jax.ShapeDtypeStruct((Mp,), np.int32, sharding=mach),
            jax.ShapeDtypeStruct((), np.int32, sharding=mach),
            alpha=ALPHA, max_rounds=default_fuse(), smax=dev.smax,
            analytic_init=True,
        ).compile()


def test_dense_auction_solve_compiles(small_dense, one_chip):
    compiled = _compile_solve(_shapes(small_dense, one_chip))
    assert "all-reduce" not in compiled.as_text()


def test_sharded_round_compiles_over_four_chips(flagship, topo, no_cache):
    """The ``--mesh_width=4`` production lane: the fused round laid out
    by ``resident_round_shardings`` over a 2x2 mesh at flagship width."""
    from poseidon_tpu.parallel.sharded import resident_round_shardings

    f = flagship
    mesh = Mesh(np.array(topo.devices[:4]), ("tasks",))
    in_spec, dt_spec = resident_round_shardings(mesh, f.dt)
    dt = jax.tree_util.tree_map(lambda x, sh: _shapes(x, sh), f.dt, dt_spec)
    with enable_x64(True):
        compiled = res._resident_chain.lower(
            dt, _shapes(f.inputs, in_spec), **_resident_kw(f),
        ).compile()
    txt = compiled.as_text()
    assert "all-reduce" in txt and "all-gather" in txt
