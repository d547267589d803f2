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


def test_the_big_days_largest_group_densifies_into_bf16_on_one_chip(topo):
    """Group (87, 4096, 32) of `flow20_bigday_fit` into its 2-byte store
    (the plan takes W-major there): scattered whole in float32 and cast at
    the end it asked 16.31 GB of 15.75 GB (PERF.md, PR 41); a batch at a
    time it holds one batch's float32 block beside the stored stack."""
    one = SingleDeviceSharding(topo.devices[0])
    sparse = [jax.ShapeDtypeStruct((87, ROWS, 32), dtype, sharding=one)
              for dtype in (jnp.int32, jnp.float32)]
    mem = fused.densify_stack.lower(
        *sparse, num_terms=V, width=None, dtype=jnp.bfloat16,
        wmajor=True).compile().memory_analysis()
    assert mem.output_size_in_bytes == 87 * ROWS * V * 2
    assert mem.temp_size_in_bytes < 2 * ROWS * V * 4


def test_the_feedback_days_largest_group_densifies_into_int16_on_one_chip(
        topo):
    """Group (87, 4096, 32) of `flow20_feedback_fit` into the integer rung
    of the store ladder: int16, 2 B a cell, a batch's float32 block of
    summed cells cast exactly into its slot, as the bfloat16 store is."""
    one = SingleDeviceSharding(topo.devices[0])
    sparse = [jax.ShapeDtypeStruct((87, ROWS, 32), dtype, sharding=one)
              for dtype in (jnp.int32, jnp.float32)]
    mem = fused.densify_stack.lower(
        *sparse, num_terms=V, width=None, dtype=jnp.int16,
        wmajor=True).compile().memory_analysis()
    assert mem.output_size_in_bytes == 87 * ROWS * V * 2
    assert mem.temp_size_in_bytes < 2 * ROWS * V * 4


def test_the_wmajor_kernel_reads_an_int16_stack_in_place_on_one_chip(topo):
    """The W-major kernel over the feedback day's int16 stack [87, 8192,
    4096], a batch read in place by its index: Mosaic lowers the 16-bit
    integer block and its promote to float32 (it lowers no uint16 and no
    uint8 one), and the program holds no float32 copy of a batch."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def e_step(lb, a, stack, m, g, w, n):
        return dense_estep.e_step_dense(
            lb, a, stack, m, var_max_iters=20, var_tol=1e-6, wmajor=True,
            gamma_prev=g, warm=w, precision="bf16", batch_index=n)

    compiled = jax.jit(e_step).lower(
        shape((K, V)), shape(()), shape((87, V, ROWS), jnp.int16),
        shape((ROWS,)), shape((ROWS, K)), shape((), jnp.int32),
        shape((), jnp.int32)).compile()
    assert _kernels(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < ROWS * V * 4
    assert not [rest[:60] for _, rest in _instructions(compiled)
                if re.match(rf"f32\[{V},{ROWS}\]", rest)]



def test_the_rowmajor_kernel_packs_the_int16_products_on_one_chip(topo):
    """The row-major kernel over an int16 batch [4096, 8192]: Mosaic lowers
    the packed products' lane-wise stacks, the exact placement products
    and the gamma update's lane slices at offsets of 24 (the W-major
    kernel's are sublane-wise: the test above)."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def e_step(lb, a, c, m, g, w):
        return dense_estep.e_step_dense(
            lb, a, c, m, var_max_iters=20, var_tol=1e-6, gamma_prev=g,
            warm=w, precision="bf16")

    compiled = jax.jit(e_step).lower(
        shape((K, V)), shape(()), shape((ROWS, V), jnp.int16),
        shape((ROWS,)), shape((ROWS, K)), shape((), jnp.int32)).compile()
    assert _kernels(compiled)
    assert dense_estep.kernel_products(jnp.int16, K) == "split6_packed"


@pytest.mark.parametrize("rows", [ROWS, 3968, 3456])
def test_the_wmajor_kernel_packs_the_products_over_a_float32_stack(
        topo, rows):
    """The day after analyst feedback on the default 4 B store
    (`flow20_feedback_f32_fit`, flow20_fit's shapes): the W-major kernel
    reads a batch of a float32 stack in place and takes the packed
    products (`accurate`), at a full batch's block of 256 documents and
    the tails' of 128: Mosaic fits the float32 block and the packed
    operands in the VMEM the kernel asks for."""
    assert dense_estep.pick_block_w(rows, V, K, "f32", jnp.float32,
                                    True) == (256 if rows == ROWS else 128)
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def e_step(lb, a, stack, m, g, w, n):
        return dense_estep.e_step_dense(
            lb, a, stack, m, var_max_iters=20, var_tol=1e-6, wmajor=True,
            gamma_prev=g, warm=w, batch_index=n, accurate=True)

    compiled = jax.jit(e_step).lower(
        shape((K, V)), shape(()), shape((2, V, rows)), shape((rows,)),
        shape((rows, K)), shape((), jnp.int32),
        shape((), jnp.int32)).compile()
    assert _kernels(compiled)
    assert not [rest[:60] for _, rest in _instructions(compiled)
                if re.match(rf"f32\[{V},{rows}\]", rest)]


@pytest.mark.parametrize("rows", [3968, 3456])
def test_the_wmajor_kernel_compiles_at_flow20_fits_tails_on_one_chip(
        topo, rows):
    """`flow20_fit`'s two tails as `dense_estep.tail_rows` pads them, 3,968
    and 3,456 rows of the 128-lane tile (the 8-row tile's 3,952 and 3,352
    refuse the W-major block): a float32 batch [8192, rows], blocks of 128
    documents."""
    assert dense_estep.pick_block_w(rows, V, K) == 128
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def e_step(lb, a, dense_t, m, g, w):
        return dense_estep.e_step_dense(
            lb, a, dense_t, m, var_max_iters=20, var_tol=1e-6, wmajor=True,
            gamma_prev=g, warm=w)

    compiled = jax.jit(e_step).lower(
        shape((K, V)), shape(()), shape((V, rows)), shape((rows,)),
        shape((rows, K)), shape((), jnp.int32)).compile()
    assert _kernels(compiled)


@pytest.mark.parametrize("rows", [3968, 3456])
def test_the_sharded_wmajor_kernel_compiles_at_flow20_fit_dp4s_tails(
        mesh, rows):
    """`flow20_fit_dp4`'s two tails as `dense_estep.tail_rows` pads them,
    `rows` a shard of four: the `shard_map`'d W-major kernel over a batch
    [8192, 4 x rows] with the documents over `data`, under its trace name."""
    e_step = sharded.bound(
        sharded.make_data_parallel_dense_e_step(mesh, wmajor=True),
        var_max_iters=20, var_tol=1e-6, interpret=False)
    rep, docs = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def shape(dims, s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=s)

    b = 4 * rows
    compiled = jax.jit(e_step).lower(
        shape((K, V), rep), shape((), rep),
        shape((V, b), NamedSharding(mesh, P(None, "data"))),
        shape((b,), docs), shape((b, K), docs),
        shape((), rep, jnp.int32)).compile()
    kernels = _kernels(compiled)
    assert kernels and all(re.match(r"tpu_custom_call(\.\d+)?$", name)
                           for name in kernels), kernels
