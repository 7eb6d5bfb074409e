"""Compiles for a described (not attached) TPU v5e: the order-statistics
kernel at the main path's real widths, and Algorithm 1 sharded over a
2x2 machine mesh. Nothing runs; the chip's compiler refuses what it
could not run (block shapes, VMEM, kernels it cannot partition).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.agg import ostat_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # any failure means it cannot be described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,op", [
    ((50, 10), "median"),                  # one protocol round, fig-eps
    ((8, 4096), "dcq_mad"),                # mid-p gradient leaf
    ((16, 262144), "median_mad_dcq"),      # large p, fused three outputs
    ((320, 8, 10), "dcq_mad"),             # batched: the sweep regime
    ((320, 8, 10), "dcq"),                 # batched with a scale operand
])
def test_ostat_pallas_compiles_for_v5e(one_chip, shape, op):
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)]
    _assert_kernel(op, args)


@pytest.mark.parametrize("shape", [(1536, 4, 8), (50304, 4, 768)])
def test_ostat_pallas_bf16_compiles_for_v5e(one_chip, shape):
    """bf16 model leaves as the QN trainer's wire lays them out, (rows,
    machines, cols) with 4 machines: blocks stay bf16."""
    _assert_kernel("dcq_mad", [jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                                    sharding=one_chip)])


def _assert_kernel(op, args):
    """Compile ``ostat_pallas(*args)`` natively; a ``dcq`` scale is added."""
    v = args[0]
    if op == "dcq":
        args.append(jax.ShapeDtypeStruct(v.shape[:-2] + v.shape[-1:],
                                         jnp.float32, sharding=v.sharding))
    compiled = jax.jit(
        lambda v, *sc: ostat_pallas(v, op, *sc, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def tpu_branch(monkeypatch):
    """Steer dispatch and ostat_pallas onto their TPU branch (there is no
    chip here); traces made on it must not serve a later CPU call."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def test_sharded_protocol_compiles_for_v5e_2x2(topo, tpu_branch):
    """run_sharded's engine over a 4-chip machine mesh, with the kernel
    the TPU dispatch picks: each center aggregation must be a kernel the
    compiler accepts on the mesh (``center_map`` runs it whole on every
    device)."""
    from repro.configs.base import ProtocolConfig
    from repro.core import DPQNProtocol, get_problem
    from repro.dist.sharded_protocol import center_map, machine_map

    mesh = Mesh(topo.devices, ("machines",), axis_types=(AxisType.Auto,))
    rows = NamedSharding(mesh, PartitionSpec("machines"))
    X = jax.ShapeDtypeStruct((64, 1000, 10), jnp.float32, sharding=rows)
    y = jax.ShapeDtypeStruct((64, 1000), jnp.float32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    proto = DPQNProtocol(get_problem("logistic"),
                         ProtocolConfig(noiseless=True),
                         machine_map=machine_map(mesh),
                         center=center_map(mesh))
    single, _ = proto._engine("scale")
    lowered = single.lower(key, X, y, None, None, None, -3.0)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_sharded_qn_train_step_compiles_for_v5e_2x2(topo, tpu_branch):
    """The QN trainer's ``--sharded`` step (machines over a 4-chip mesh,
    GSPMD for the machine-local rounds) with the kernel the TPU dispatch
    picks for every leaf: the center's kernels must compile on the mesh."""
    from repro.configs import get_config
    from repro.configs.base import TreeProtocolConfig
    from repro.models.model import Model
    from repro.train.trainer import QNTrainConfig, QNTrainer

    mesh = Mesh(topo.devices, ("machines",), axis_types=(AxisType.Auto,))
    repl = NamedSharding(mesh, PartitionSpec())
    model = Model(get_config("xlstm-125m", reduced=True), remat=True)
    trainer = QNTrainer(model, QNTrainConfig(
        n_machines=4, protocol=TreeProtocolConfig(hist=1)), mesh=mesh)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
            tree)
    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    mem = placed(jax.eval_shape(trainer.init_memory, params))
    batch = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=repl)
             for k in ("tokens", "labels")}
    lowered = trainer.step_fn.lower(params, mem, batch, key, None)
    assert "tpu_custom_call" in lowered.compile().as_text()


def _innermost_alg1_scope(op_name):
    """The innermost of Algorithm 1's name scopes on an op's path (under
    vmap a scope reads ``vmap(alg1.center)``), or None."""
    import re
    from repro import spans
    found = re.findall(r"(?:^|[/(])(%s)(?=[/)]|$)" % "|".join(
        re.escape(s) for s in spans.ALG1_SCOPES), op_name)
    return found[-1] if found else None


@pytest.mark.parametrize("placement", ["one_chip", "mesh_2x2"])
def test_engine_scopes_survive_the_tpu_compiler(topo, tpu_branch,
                                                placement):
    """Algorithm 1's engine compiled for v5e keeps its name scopes in the
    op metadata (the profiler reads device time per scope from it), on
    one chip as ``run_monte_carlo`` runs it and sharded over a 2x2
    machine mesh; every order-statistics kernel lies under the center."""
    import re
    from repro import spans
    from repro.configs.base import ProtocolConfig
    from repro.core import DPQNProtocol, get_problem
    from repro.dist.sharded_protocol import center_map, machine_map

    prob, cfg = get_problem("logistic"), ProtocolConfig(eps=10.0, delta=0.05)
    if placement == "one_chip":
        place = SingleDeviceSharding(topo.devices[0])
        rows = repl = place
        _, engine = DPQNProtocol(prob, cfg)._engine("scale")
        keys = jax.ShapeDtypeStruct((100, 2), jnp.uint32, sharding=repl)
        m = 51
    else:
        mesh = Mesh(topo.devices, ("machines",),
                    axis_types=(AxisType.Auto,))
        rows = NamedSharding(mesh, PartitionSpec("machines"))
        repl = NamedSharding(mesh, PartitionSpec())
        engine, _ = DPQNProtocol(prob, cfg, machine_map=machine_map(mesh),
                                 center=center_map(mesh))._engine("scale")
        keys = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
        m = 52
    X = jax.ShapeDtypeStruct((m, 1000, 10), jnp.float32, sharding=rows)
    y = jax.ShapeDtypeStruct((m, 1000), jnp.float32, sharding=rows)
    byz = jax.ShapeDtypeStruct((m - 1,), jnp.bool_, sharding=repl)
    text = engine.lower(keys, X, y, byz, None, None, -3.0).compile().as_text()
    scopes = {_innermost_alg1_scope(n)
              for n in re.findall(r'op_name="([^"]*)"', text)}
    assert set(spans.ALG1_SCOPES) <= scopes
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels
    for ln in kernels:
        op = re.search(r'op_name="([^"]*)"', ln)
        assert op and _innermost_alg1_scope(op.group(1)) == spans.ALG1_CENTER
