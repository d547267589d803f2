"""The four-chip fit's programs, compiled for a v5e 2x2 that is described
and not attached, at the shapes of the benchmark's cell `flow20_fit_dp4`
(K=20, V=8,192, 4,096 rows a device).  Nothing runs: this holds what only
the chip's compiler decides and the CPU suite cannot see, at no chip time:
what the device trace will call the E-step kernel under a mesh, and that
the sharded densify fits a chip and moves nothing between chips.

The topology is described inside a fixture, by the one worker that is given
this file; every compile of the suite for a described chip belongs here.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from oni_ml_tpu.models import fused
from oni_ml_tpu.ops import dense_estep
from oni_ml_tpu.parallel import sharded

K, V, ROWS = 20, 8192, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An executable compiled for a described chip is written to the
    # persistent cache and cannot be read back without one.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # What a run has: plans.warmup.setup_compilation_cache turns the full
    # tracebacks off, and the names XLA gives instructions depend on it.
    full = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    yield described
    jax.config.update("jax_include_full_tracebacks_in_locations", full)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _instructions(compiled) -> list:
    """(name, rest of the line) of every HLO instruction."""
    return [(m.group(1), m.group(2)) for m in (
        re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*)", line)
        for line in compiled.as_text().splitlines()) if m]


def _kernels(compiled) -> list:
    """Names of the instructions that run a Mosaic kernel."""
    return [name for name, rest in _instructions(compiled)
            if 'custom_call_target="tpu_custom_call"' in rest
            or "kind=kCustom" in rest]


def _accumulator_over(dense_e_step, batches, sharding):
    """The chunk program's accumulator (fused.make_em_accumulator) over one
    dense group of `batches` stacked batches, around `dense_e_step`;
    lowered on `sharding`s."""
    accumulate = fused.make_em_accumulator(
        num_topics=K, num_terms=V, var_max_iters=20, var_tol=1e-6,
        dense_e_step_fn=dense_e_step, warm_start=True)
    rep, rows = sharding
    b = ROWS * (4 if isinstance(rows, NamedSharding) else 1)

    def shape(dims, s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=s)

    return jax.jit(accumulate).lower(
        shape((K, V), rep), shape((), rep),
        ((shape((batches, b, V), rows), shape((batches, b), rows)),),
        (shape((batches, b, K), rows),), shape((), rep, jnp.bool_)).compile()


def _one_device_e_step(lb, a, d, m, g, w, batch_index=None):
    # what the accumulator's default calls, for the chip (the default asks
    # `jax.default_backend()`, the CPU here, and would interpret)
    return dense_estep.e_step_dense(
        lb, a, d, m, var_max_iters=20, var_tol=1e-6, gamma_prev=g, warm=w,
        batch_index=batch_index)


_one_device_e_step._oni_stack_capable = True


def test_the_trace_calls_the_kernel_the_same_with_and_without_a_mesh(
        topo, mesh):
    """The benchmark's readers find the E-step kernel by the name the
    device trace gives its instruction (benchmarks/jobs/fit_trace.py:
    `tpu_custom_call`).  Under a bare `shard_map` XLA called it
    `shard_map.<n>` and a sharded fit's kernels ran out of their sight.
    The kernel's corpus operand is the group's whole stack (PR 37), and the
    compiled loop holds no operation that yields a whole batch of it: XLA
    copied every batch out of the stack for a kernel that took one batch
    (`dynamic-slice_bitcast_fusion f32[4096,8192]`, a quarter of the
    device's time)."""
    one = SingleDeviceSharding(topo.devices[0])
    plain = _accumulator_over(_one_device_e_step, 3, (one, one))
    e_step = sharded.bound(
        sharded.make_data_parallel_dense_e_step(mesh),
        var_max_iters=20, var_tol=1e-6, interpret=False)
    meshed = _accumulator_over(
        e_step, 3, (NamedSharding(mesh, P()),
                    NamedSharding(mesh, P(None, "data"))))
    for compiled in (plain, meshed):
        kernels = _kernels(compiled)
        assert kernels
        assert all(re.match(r"tpu_custom_call(\.\d+)?$", name)
                   for name in kernels), kernels
        # and nothing but a kernel bears that name
        assert sorted(kernels) == sorted(
            name for name, _ in _instructions(compiled)
            if name.startswith("tpu_custom_call"))
        whole_batch = [
            (name, rest[:60]) for name, rest in _instructions(compiled)
            if re.match(rf"f32\[(1,)?{ROWS},{V}\]", rest)]
        assert not whole_batch, whole_batch
        # the stack itself is only ever passed on: a parameter, a tuple's
        # element, the kernel's operand
        assert {re.match(r"\S+ ([\w\-]+)\(", rest).group(1)
                for _, rest in _instructions(compiled)
                if rest.startswith(f"f32[3,{ROWS},{V}]")
                } <= {"parameter", "get-tuple-element"}
        assert compiled.memory_analysis().temp_size_in_bytes < ROWS * V * 4
    text = meshed.as_text()
    assert " all-reduce(" in text and " all-gather(" not in text


@pytest.mark.parametrize("wmajor", [False, True])
def test_sharded_densify_of_the_cells_largest_group_fits_a_chip(
        mesh, wmajor):
    """Group (31, 16384, 32) of `flow20_fit_dp4`: left to itself XLA
    gathered the dense stack onto every device, 19.38 GB of 15.75 GB
    (PERF.md, PR 29)."""
    rows = NamedSharding(mesh, P(None, "data"))
    sparse = [jax.ShapeDtypeStruct((31, 4 * ROWS, 32), dtype, sharding=rows)
              for dtype in (jnp.int32, jnp.float32)]
    compiled = fused.densify_stack.lower(
        *sparse, num_terms=V, width=None, dtype=jnp.float32, wmajor=wmajor,
        mesh=mesh).compile()
    mem = compiled.memory_analysis()
    dense_bytes = 31 * ROWS * V * 4
    assert mem.output_size_in_bytes == dense_bytes
    # the compiler refuses what does not fit; the scatter's scratch is one
    # more copy of the device's own rows, not of the whole stack
    assert mem.temp_size_in_bytes < 1.01 * dense_bytes
    assert not re.search(
        r" (all-gather|all-reduce|all-to-all|collective-permute)\(",
        compiled.as_text())
    (out,) = compiled.output_shardings if isinstance(
        compiled.output_shardings, (tuple, list)) else (
        compiled.output_shardings,)
    assert out.spec == (P(None, None, "data") if wmajor
                        else P(None, "data"))
