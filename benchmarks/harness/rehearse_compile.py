"""Third rehearsal: a fit cell's EM chunk program at its REAL size, compiled
for a described v5e:2x2 that is not attached, with memory_analysis().

It hands the program's own builders (fused.make_chunk_runner and, with a
mesh, sharded.make_data_parallel_dense_e_step) the shapes a cell's batches
have, as ShapeDtypeStructs on the described devices.  The program asks
jax.default_backend() to choose between its kernels and their interpreter;
here that answer is steered to "tpu" for the length of the lowering (a
rehearsal's business, not an option of the program).  The dense layout is
chosen as models/lda.py `_fused_loop` chooses it, and the placement's
densify is the program's own `fused.densify_stack`, under the cell's mesh
where it has one (`densify_peak_bytes`).  Nothing runs: this says
what the chip's compiler accepts and how many bytes the program holds, not
how fast it is.
"""

from __future__ import annotations

import os
from collections import Counter
from functools import partial
from types import SimpleNamespace
from unittest import mock

import numpy as np

from benchmarks.harness import cells, corpus_gen


def batch_shapes(traffic: dict, num_terms: int, data: int) -> list:
    """(B, L) of every batch the program's make_batches gives the cell's
    length multiset (contents do not matter)."""
    from oni_ml_tpu.io.corpus import Corpus, make_batches

    lengths = corpus_gen.length_multiset(
        traffic["num_docs"], traffic["corpus"]["length"], num_terms)
    ptr = np.r_[0, np.cumsum(lengths)].astype(np.int64)
    corpus = Corpus(doc_names=[""] * len(lengths), vocab=[],
                    doc_ptr=ptr, word_idx=np.zeros(ptr[-1], np.int32),
                    counts=np.ones(ptr[-1], np.int32))
    return [b.word_idx.shape for b in make_batches(
        corpus, batch_size=traffic["batch_size"], pad_multiple=8 * data)]


def describe_topology():
    """A v5e 2x2 that is described and not attached."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def cell_layout(name: str, topo) -> SimpleNamespace | None:
    """What the placement and the chunk program of a fit cell see of it on
    the described devices: the batches by shape, the dense layout chosen as
    models/lda.py `_fused_loop` chooses it, the mesh and the shardings.
    Nothing for a cell whose job is not `fit`."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from oni_ml_tpu.ops import dense_estep

    found = cells.resolve(name)
    traffic, lda = found["traffic"], found["config"]["lda"]
    if traffic["job"] != "fit":
        return None
    k, v = lda["num_topics"], found["config"]["num_terms"]
    data = traffic["mesh"][0] if traffic.get("mesh") else 1
    shapes = batch_shapes(traffic, v, data)
    local = sorted({b // data for b, _ in shapes})
    wmajor = all(dense_estep.pick_block_w(b, v, k, "f32") for b in local)
    by_shape = dict(Counter(shapes))
    if data > 1:
        mesh = Mesh(np.array(topo.devices[:data]).reshape(data, 1),
                    ("data", "model"))
        rep = NamedSharding(mesh, P())
        docs = NamedSharding(mesh, P(None, None, "data") if wmajor
                             else P(None, "data"))
        rows = NamedSharding(mesh, P(None, "data"))
    else:
        mesh = None
        rep = docs = rows = SingleDeviceSharding(topo.devices[0])
    return SimpleNamespace(
        traffic=traffic, lda=lda, k=k, v=v, data=data, local=local,
        wmajor=wmajor, by_shape=by_shape, mesh=mesh, rep=rep, docs=docs,
        rows=rows)


def densify_peak_bytes(layout) -> int:
    """The most bytes a device holds while the placement densifies one shape
    group: arguments, output and scratch of `fused.densify_stack`, the
    program's own jit, under the cell's mesh where it has one (every device
    scatters its own rows; a jit of this file's own let XLA gather the whole
    dense stack onto every device, the failure PR 29 repaired in the
    program: 19.38 GB of 15.75 GB for `flow20_fit_dp4`)."""
    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused

    peak = 0
    for (b, length), nb in sorted(layout.by_shape.items()):
        sparse = [jax.ShapeDtypeStruct((nb, b, length), dt,
                                       sharding=layout.rows)
                  for dt in (jnp.int32, jnp.float32)]
        placed = fused.densify_stack.lower(
            *sparse, num_terms=layout.v, width=None, dtype=jnp.float32,
            wmajor=layout.wmajor, mesh=layout.mesh,
        ).compile().memory_analysis()
        peak = max(peak, placed.output_size_in_bytes
                   + placed.temp_size_in_bytes
                   + placed.argument_size_in_bytes)
    return peak


def compile_cell(name: str) -> dict:
    import jax
    import jax.numpy as jnp

    from oni_ml_tpu.models import fused
    from oni_ml_tpu.ops import dense_estep
    from oni_ml_tpu.parallel import sharded

    layout = cell_layout(name, describe_topology())
    if layout is None:
        print(f"{name}: no compile rehearsal for a job that is not 'fit'")
        return {}
    lda, k, v, wmajor = layout.lda, layout.k, layout.v, layout.wmajor
    width = dense_estep.padded_width(v)
    blocks = {b: dense_estep.pick_block(b, v, k, "f32") for b in layout.local}
    kib = max(filter(None, (
        dense_estep.scoped_vmem_kib(b, v, k, wmajor=wmajor, precision="f32")
        for b in layout.local)), default=None)
    dense_fn = None
    if layout.mesh is not None:
        dense_fn = partial(
            sharded.make_data_parallel_dense_e_step(
                layout.mesh, wmajor=wmajor, precision="f32"),
            var_max_iters=lda["var_max_iters"], var_tol=lda["var_tol"],
            interpret=False)

    def shape(dims, dtype, sharding):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    groups, gammas = [], []
    for (b, _), nb in sorted(layout.by_shape.items()):
        dims = (nb, width, b) if wmajor else (nb, b, width)
        groups.append((shape(dims, jnp.float32, layout.docs),
                       shape((nb, b), jnp.float32, layout.rows)))
        gammas.append(shape((nb, b, k), jnp.float32, layout.rows))
    runner = fused.make_chunk_runner(
        num_docs=layout.traffic["num_docs"], num_topics=k, num_terms=v,
        chunk=128, var_max_iters=lda["var_max_iters"],
        var_tol=lda["var_tol"], em_tol=lda["em_tol"],
        estimate_alpha=lda["estimate_alpha"], dense_wmajor=wmajor,
        warm_start=lda["warm_start"], dense_e_step_fn=dense_fn,
        dense_precision="f32", alpha_max_iters=lda["alpha_max_iters"],
        compiler_options={"xla_tpu_scoped_vmem_limit_kib": str(kib)}
        if kib else None)
    # Placement: the program densifies each sparse stack in a jit of its own
    # (fused.densify_stack, dispatched by fused.densify_groups) before the
    # chunk program ever runs.
    place_bytes = densify_peak_bytes(layout)
    scalar = partial(shape, (), sharding=layout.rep)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = runner.jitted.lower(
            shape((k, v), jnp.float32, layout.rep), scalar(jnp.float32),
            scalar(jnp.float32), tuple(groups), scalar(jnp.int32),
            tuple(gammas), scalar(jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    out = {
        "cell": name, "batches": {f"{b}x{l}": n for (b, l), n
                                  in sorted(layout.by_shape.items())},
        "wmajor": wmajor, "doc_blocks": blocks,
        "padded_docs": sum(b * n for (b, _), n in layout.by_shape.items()),
        "argument_bytes_per_device": mem.argument_size_in_bytes,
        "temp_bytes_per_device": mem.temp_size_in_bytes,
        "output_bytes_per_device": mem.output_size_in_bytes,
        "densify_peak_bytes_per_device": place_bytes,
        "fits_16e9": max(place_bytes, mem.argument_size_in_bytes
                         + mem.temp_size_in_bytes) < 16e9,
        "kernels": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce("),
    }
    print(out)
    return out
