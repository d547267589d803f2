"""Device-resident chunked EM: N iterations per jit call.

The baseline trainer (lda.py) dispatches one E-step per batch per EM
iteration and syncs the likelihood to the host every iteration to decide
convergence.  That host round-trip is dead time on the device, once per
iteration (per-dispatch cost, not measured on the current machine).

Here the whole EM loop body — scan over batches, suff-stats accumulate,
M-step, Newton alpha, convergence check — runs inside ONE compiled
program as a `lax.while_loop`, executing up to `chunk` EM iterations
before returning control.  The host only syncs at chunk boundaries to
stream `likelihood.dat`, fire progress callbacks, and checkpoint; the
convergence decision is made on device so a run that converges mid-chunk
stops immediately (the reference's `|Δℓ/ℓ| < em_tol` semantics, SURVEY.md
§2.8, evaluated in compute dtype); at each chunk boundary the driver
(lda.py _fused_loop) re-derives conv in float64 and that value is
authoritative, so the final stop always agrees with likelihood.dat.

Batches are grouped by (B, L) shape and stacked [NB, B, L] so each group
is one `lax.scan`; bucketed batching (io/corpus.py) produces few distinct
shapes, so the stacking adds no padding.  The E/M-step hooks are the same
ones the distributed layer substitutes (shard_map over the (data, model)
mesh, psum'd suff-stats) — the fused loop composes with both the
data-parallel and vocab-sharded plans unchanged.

There is ONE chunk implementation (`run_chunk_dispatch` in
`_build_chunk_program`): log-space beta, `m_step_fn`, the accumulator's
scan over whatever groups it is handed.  A single dense group of one
batch runs the same code as six groups of thirty-one; which E-step a
group gets is the accumulator's dispatch on the group's layout, decided
on the host by the driver's plan (models/lda.py `_plan_estep`).
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
from collections import OrderedDict
from contextlib import nullcontext
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..io import Batch
from ..ops import estep
from ..telemetry.spans import current_recorder, maybe_span


class StackedGroups(NamedTuple):
    """Shape-grouped batches, stacked for `lax.scan`.

    arrays[g] = (word_idx [NB,B,L], counts [NB,B,L], doc_mask [NB,B]);
    batch_slots[g] is the list of original batch indices, so slot j of
    group g holds batches[batch_slots[g][j]].
    """

    arrays: tuple
    batch_slots: tuple


def _run_view(arrays: Sequence[np.ndarray]) -> np.ndarray | None:
    """The [G, ...] stack of `arrays` as a view, where it already exists
    in memory: every array C-contiguous, of one dtype and shape, each one
    starting where the one before it ends, all of them views of one
    C-contiguous owner (`.base`).  That is what `make_batches` returns for
    the batches of a bucket.  A single C-contiguous array is a run of
    one, whoever owns it.  None wherever any of it cannot be seen."""
    first = arrays[0]
    if not first.flags.c_contiguous:
        return None
    if len(arrays) == 1:
        return first.reshape(1, *first.shape)
    owner = first.base
    if not (isinstance(owner, np.ndarray) and owner.flags.c_contiguous):
        return None
    at = first.ctypes.data
    for a in arrays:
        if not (a.base is owner and a.flags.c_contiguous
                and a.dtype == first.dtype and a.shape == first.shape
                and a.ctypes.data == at):
            return None
        at += a.nbytes
    return np.ndarray(
        (len(arrays), *first.shape), first.dtype, buffer=owner,
        offset=first.ctypes.data - owner.ctypes.data)


def stack_run(arrays: Sequence[np.ndarray], dtype=None):
    """`np.stack(arrays)` cast to `dtype` (None: as they are), and the
    bytes the host wrote to make it.  Arrays that lie end to end in one
    buffer (`_run_view`) are handed on as a view of it: nothing written,
    nothing allocated, and the view keeps the buffer alive.  Anything else
    is stacked, and cast, in ONE pass into a fresh array.  The same values
    either way."""
    view = _run_view(arrays)
    if view is None:
        out = np.stack(arrays, dtype=dtype, casting="unsafe")
    elif dtype is None or view.dtype == dtype:
        return view, 0
    else:
        out = view.astype(dtype)
    return out, out.nbytes


def stack_batches(
    batches: Sequence[Batch],
    dtype,
    put: Callable[[np.ndarray], jax.Array],
) -> StackedGroups:
    """Group batches by (B, L) and stack each group along a new leading
    axis.  `put` commits the stacked [NB, ...] arrays to device (on a
    mesh: shard the batch axis, axis 1).  The span's `h2d_bytes` is the
    host arrays' size: on a mesh, the sum over the devices, each of which
    receives its own rows.

    A group's word ids and counts are VIEWS of the batches' own buffer
    wherever the batches lie in it end to end (`stack_run`: every group
    of `make_batches`' list, whose buffers therefore have to live, and
    stay unwritten, until the puts have been consumed: the fit holds the
    batches to its end); batches some caller copied, reordered or built
    one by one are `np.stack`ed as before.  Which of the two a group took
    is `copied_bytes`, what the host wrote for it: the masks alone (one
    small array a batch, allocated apart, always stacked) on views, all
    of `bytes` otherwise.

    Per group the span holds `fit.stack.copy` (assembling the host stack:
    views, or the copy) and `fit.stack.put` (handing it to the runtime;
    `shards`: the devices it went to), each counting its `bytes`; the
    copy and `fit.stack` also count `copied_bytes`."""
    groups: dict[tuple, list[int]] = {}
    for i, b in enumerate(batches):
        groups.setdefault(b.word_idx.shape, []).append(i)
    arrays = []
    slots = []
    with maybe_span("fit.stack", groups=len(groups)) as sp:
        h2d_bytes = copied_bytes = 0
        for shape in sorted(groups):
            idxs = groups[shape]
            with maybe_span("fit.stack.copy") as sub:
                widx, wrote_w = stack_run(
                    [batches[i].word_idx for i in idxs])
                cnts, wrote_c = stack_run(
                    [batches[i].counts for i in idxs], dtype)
                mask = np.stack([batches[i].doc_mask for i in idxs],
                                dtype=dtype, casting="unsafe")
                host = (widx, cnts, mask)
                nbytes = sum(a.nbytes for a in host)
                wrote = wrote_w + wrote_c + mask.nbytes
                sub.annotate(bytes=nbytes, copied_bytes=wrote)
            h2d_bytes += nbytes
            copied_bytes += wrote
            with maybe_span("fit.stack.put") as sub:
                arrays.append(tuple(put(a) for a in host))
                if sub.live:
                    sub.annotate(
                        bytes=nbytes,
                        shards=len(arrays[-1][0].sharding.device_set))
            slots.append(tuple(idxs))
        sp.annotate(h2d_bytes=h2d_bytes, copied_bytes=copied_bytes)
    return StackedGroups(tuple(arrays), tuple(slots))


@functools.partial(
    jax.jit,
    static_argnames=("num_terms", "width", "dtype", "wmajor", "mesh"))
def densify_stack(widx, cnts, *, num_terms, width, dtype, wmajor,
                  mesh=None):
    """One stacked group's token lists [NB,B,L] -> dense counts [NB,B,W]
    ([NB,W,B] with `wmajor`).  ONE jitted function for the process, the
    layout its static arguments: a group shape a previous fit densified
    dispatches the executable jax kept for it, where a fresh `jax.jit`
    per group and fit was traced, lowered and fetched again every
    time.

    With `mesh` (the data-parallel fit: the stacks' document axis is
    sharded over `data`) the scatter runs under `shard_map`: every device
    densifies its own rows, and the output keeps the input's document
    sharding, `P(None, data)` row-major and `P(None, None, data)`
    W-major.  No collective, and nothing for a later `device_put` to
    move.  Left to itself XLA gathered the whole dense stack onto every
    device first (PERF.md, PR 29)."""
    from ..ops import dense_estep

    def one(w, c):
        d = dense_estep.densify(w, c, num_terms, width=width, dtype=dtype)
        return d.T if wmajor else d

    stack = jax.vmap(one)
    if mesh is None:
        return stack(widx, cnts)
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    docs = P(None, DATA_AXIS)
    return jax.shard_map(
        stack, mesh=mesh, in_specs=(docs, docs),
        out_specs=P(None, None, DATA_AXIS) if wmajor else docs,
    )(widx, cnts)


def densify_groups(
    groups: StackedGroups, num_terms: int, wmajor: bool = False,
    put: Callable | None = None, width: int | None = None,
    dtype=None, mesh=None,
) -> StackedGroups:
    """Convert stacked sparse groups to dense-counts groups for the
    gather/scatter-free E-step (ops/dense_estep.py).

    Each group (word_idx [NB,B,L], counts [NB,B,L], mask [NB,B]) becomes
    (dense_counts [NB,B,V], mask [NB,B]) — or [NB,V,B] with `wmajor`,
    the transposed layout the W-major kernel consumes.  The scatter runs
    ONCE here and is amortized over every EM iteration of the run — that
    amortization is the whole point (a per-iteration scatter is what the
    dense path exists to avoid).  `width` overrides the dense width (the
    vocab-sharded XLA path matches it to the sharded beta width);
    `dtype` is the storage dtype (dense_estep.corpus_dtype — bf16 when
    exact, halving the corpus' HBM footprint and streaming).

    `mesh`: the stacks are sharded over its `data` axis and each device
    densifies its own documents (`densify_stack`); the dense groups come
    out in the layout the data-parallel kernel reads, so no `put` follows.
    `put` is for a layout the scatter cannot give itself (the
    vocab-sharded plan's columns over `model`).

    The span says whether any group's program had to be `built` or all
    were `reused` from an earlier call of this process, whether the
    scatter ran `sharded`, and the dense corpus' bytes: `dense_bytes`
    over all devices, `dense_bytes_device` on each one."""
    arrays = []
    with maybe_span("fit.densify", groups=len(groups.arrays),
                    sharded=mesh is not None) as sp:
        built0 = densify_stack._cache_size()
        for widx, cnts, mask in groups.arrays:
            dense = densify_stack(widx, cnts, num_terms=num_terms,
                                  width=width, dtype=dtype, wmajor=wmajor,
                                  mesh=mesh)
            if put is not None:
                dense = put(dense)
            arrays.append((dense, mask))
        # The span closes when the dense corpus is on the device.  A fit
        # whose programs are reused enqueues its chunk program
        # milliseconds later: without this wait that program's buffers
        # are allocated while the sparse stacks above are still held
        # (22 MB more at the peak on the v5e, PERF.md), and the device's
        # densify time passes for the first host sync's.  A fit that
        # builds its programs waited here anyway, seconds, in its trace.
        jax.block_until_ready([d for d, _ in arrays])
        sp.annotate(
            dense_bytes=sum(d.nbytes for d, _ in arrays),
            dense_bytes_device=sum(
                int(np.prod(d.sharding.shard_shape(d.shape)))
                * d.dtype.itemsize for d, _ in arrays),
            program=("built" if densify_stack._cache_size() > built0
                     else "reused"),
        )
    return StackedGroups(tuple(arrays), groups.batch_slots)


def dense_groups_bytes(batches: Sequence[Batch], num_terms: int,
                       itemsize: int = 4) -> int:
    """Device bytes the densified corpus would occupy."""
    from ..ops import dense_estep

    width = dense_estep.padded_width(num_terms)
    return sum(b.word_idx.shape[0] for b in batches) * width * itemsize


class CompactPlan(NamedTuple):
    """Host-side plan for the compact-vocab dense E-step (config 4's
    single-chip MXU path — SURVEY.md §5.7's V scaling axis, the
    combinatorial word space of dns_pre_lda.scala:320-326).

    When the FULL vocabulary is too wide to densify ([B, padded V]
    blows the VMEM/HBM budget), each batch still touches only the
    words its documents contain — power-law distributed in real
    traffic, so a 4096x128-token batch of a 500k-word day typically
    holds a few tens of thousands of distinct words.  Remapping each
    batch onto its own compacted vocabulary turns the huge-V E-step
    back into the gather/scatter-free dense kernel at width Wc << V,
    at the cost of ONE [K, Wc] beta-column gather and one [Wc, K]
    suff-stats row-scatter per batch per EM iteration (vs the sparse
    path's per-token gathers in every fixed-point iteration).

    uniques[g][j]: sorted distinct word ids of group g's j-th stacked
    batch; widths[g]: the group's shared compact width (max unique
    count, padded to the 128-lane tile).
    """

    uniques: tuple          # per group: tuple of np.ndarray word ids
    widths: tuple           # per group: int compact width Wc
    wmajor: bool
    corpus_bytes: int       # device bytes of the compacted corpus


def plan_compact(
    batches: Sequence[Batch],
    num_topics: int,
    precision: str = "f32",
    wmajor: bool = True,
    itemsize: int = 4,
    local_div: int = 1,
) -> CompactPlan | None:
    """Build a CompactPlan, or None when some group's compact width
    admits no VMEM-feasible doc block (then the sparse path is the
    only option).  Pure host-side: np.unique over each batch's token
    ids (the corpus is static, so the per-batch vocabulary is fixed
    for the whole run).  `local_div` divides the per-kernel doc count
    (data-mesh shard factor); callers gate mesh support themselves."""
    from ..ops import dense_estep

    groups: dict[tuple, list[int]] = {}
    for i, b in enumerate(batches):
        groups.setdefault(b.word_idx.shape, []).append(i)
    uniques, widths = [], []
    total = 0
    use_wmajor = wmajor
    for shape in sorted(groups):
        idxs = groups[shape]
        us = tuple(np.unique(batches[i].word_idx) for i in idxs)
        wc = max(len(u) for u in us)
        wc = -(-wc // 128) * 128  # lane tile, like padded_width()
        b_local = shape[0] // local_div
        if dense_estep.pick_block(b_local, wc, num_topics,
                                  precision) is None:
            return None
        use_wmajor = use_wmajor and (
            dense_estep.pick_block_w(b_local, wc, num_topics, precision)
            is not None
        )
        uniques.append(us)
        widths.append(wc)
        total += len(idxs) * shape[0] * wc * itemsize
    return CompactPlan(tuple(uniques), tuple(widths), use_wmajor, total)


def compact_stack_batches(
    batches: Sequence[Batch],
    dtype,
    put: Callable[[np.ndarray], jax.Array],
    plan: CompactPlan,
    corpus_store=None,
) -> StackedGroups:
    """Stack batches into compact-dense groups:

    arrays[g] = (dense_local [NB, B, Wc] (or [NB, Wc, B] W-major),
                 doc_mask [NB, B], vocab_map [NB, Wc] int32)

    vocab_map[j, u] is the GLOBAL word id of local column u; columns
    past the batch's unique count repeat id 0 as a sentinel — inert,
    because their local counts are zero, so the kernel produces zero
    suff-stats there and the scatter-back adds zeros to word 0.
    Token ids remap via searchsorted into the batch's sorted unique
    set (exact: every token id is a member).  Under `fit.stack`, per
    group, `fit.stack.copy` is the host's remap and stacks (all of
    them written afresh: `copied_bytes` = `bytes`) and `fit.stack.put`
    the three `put`s; the device's densify lies between them, under
    neither."""
    groups: dict[tuple, list[int]] = {}
    for i, b in enumerate(batches):
        groups.setdefault(b.word_idx.shape, []).append(i)
    arrays = []
    slots = []
    with maybe_span("fit.stack", groups=len(groups), compact=True) as sp:
        h2d_bytes = 0
        for g, shape in enumerate(sorted(groups)):
            idxs = groups[shape]
            wc = plan.widths[g]

            with maybe_span("fit.stack.copy") as sub:
                local_idx, cnts, masks, vmaps = [], [], [], []
                for j, i in enumerate(idxs):
                    u = plan.uniques[g][j]
                    local_idx.append(
                        np.searchsorted(
                            u, batches[i].word_idx).astype(np.int32)
                    )
                    cnts.append(batches[i].counts.astype(dtype))
                    masks.append(batches[i].doc_mask.astype(dtype))
                    vm = np.zeros(wc, np.int32)
                    vm[: len(u)] = u
                    vmaps.append(vm)

                host = (np.stack(local_idx), np.stack(cnts), np.stack(masks),
                        np.stack(vmaps))
                nbytes = sum(a.nbytes for a in host)
                sub.annotate(bytes=nbytes, copied_bytes=nbytes)
            h2d_bytes += nbytes
            dense = densify_stack(
                jnp.asarray(host[0]), jnp.asarray(host[1]), num_terms=wc,
                width=wc, dtype=corpus_store, wmajor=plan.wmajor,
            )
            with maybe_span("fit.stack.put") as sub:
                arrays.append((put(dense), put(host[2]), put(host[3])))
                if sub.live:
                    sub.annotate(
                        bytes=dense.nbytes + host[2].nbytes + host[3].nbytes,
                        shards=len(arrays[-1][0].sharding.device_set))
            slots.append(tuple(idxs))
        sp.annotate(h2d_bytes=h2d_bytes, copied_bytes=h2d_bytes)
    return StackedGroups(tuple(arrays), tuple(slots))


def initial_gammas(groups_arrays, k: int, dtype, dense_wmajor=False):
    """Zero gamma buffers matching ChunkResult.gammas' structure — what
    drivers pass as the first chunk's `gammas_in` (with have_prev=False)
    so that later chunks can feed `res.gammas` back WITHOUT a retrace
    (same pytree structure/shapes every call)."""
    def batch_dim(g):
        # Dense [NB,B,W] / compact-dense [NB,B,Wc] groups put docs on
        # axis 1 like sparse [NB,B,L]; the W-major layouts transpose
        # docs onto the last axis.  Compact groups are len 3 like
        # sparse but lead with the floating dense corpus (sparse leads
        # with integer word_idx) — same rule run_batch dispatches on.
        is_dense = len(g) == 2 or jnp.issubdtype(g[0].dtype, jnp.floating)
        return g[0].shape[2] if is_dense and dense_wmajor else g[0].shape[1]

    return tuple(
        jnp.zeros((g[0].shape[0], batch_dim(g), k), dtype)
        for g in groups_arrays
    )


def reads_stack_in_place(group, dense_e_step_fn: Callable | None) -> bool:
    """Whether the accumulator hands this group's E-step the group's whole
    stack and a batch index (`dense_estep.e_step_dense(..., batch_index=)`:
    the kernel's corpus BlockSpec indexes the stack's leading axis, and no
    batch is copied out of it) where it would scan over the stack's
    slices.  Decided by what the code can see, no knob: a dense group
    (`(C, mask)`) of two batches or more, whose dense callable declares
    `_oni_stack_capable`.  The default (this module's call of
    `e_step_dense`) and the data-parallel kernel
    (parallel/sharded.make_data_parallel_dense_e_step) do; the
    vocab-sharded XLA plan and a user's own `dense_e_step_fn` do not, and
    keep the per-batch contract.  A single-batch group is called directly
    on `stack[0]`, a bitcast."""
    return (
        len(group) == 2
        and group[0].shape[0] >= 2
        and (dense_e_step_fn is None
             or getattr(dense_e_step_fn, "_oni_stack_capable", False))
    )


def make_em_accumulator(
    *,
    num_topics: int,
    num_terms: int,
    var_max_iters: int,
    var_tol: float,
    e_step_fn: Callable | None = None,
    dense_e_step_fn: Callable | None = None,
    dense_wmajor: bool = False,
    dense_precision: str = "f32",
    warm_start: bool = False,
):
    """Build `accumulate(log_beta, alpha, groups, gammas_prev, warm) ->
    (suff_stats [V, K], likelihood, alpha_ss, gammas, vi_max,
    doc_sweeps)` — one EM iteration's E-step over stacked groups WITHOUT
    the M-step tail.  `vi_max` is the most sweeps any batch ran,
    `doc_sweeps` the sum over batches of the document-sweeps they ran
    (EStepResult.doc_sweeps); the device work carries the scope `estep`.

    This is the partial-sufficient-statistics return path: the chunk
    runner composes it with the M-step/alpha update inside one compiled
    program (single-process EM), while the distributed driver
    (models/lda.py `_distributed_loop`) jits it alone per document
    shard (`make_partial_runner`), reduces the partials across
    processes through parallel/allreduce, and only then runs the
    identical M-step on every rank from the reduced stats."""
    e_fn = e_step_fn or estep.e_step
    # Sparse groups warm-start only through callables that declare the
    # gamma_prev/warm kwargs (this package's e_step and its sharded
    # wrappers); a user-supplied custom e_step_fn stays fresh-start
    # rather than breaking on unexpected kwargs.
    e_warm = warm_start and getattr(e_fn, "_oni_warm_capable", False)
    k, v = num_topics, num_terms

    def _default_dense(log_beta, alpha, dense, m, g_in, warm,
                       batch_index=None):
        from ..ops import dense_estep

        return dense_estep.e_step_dense(
            log_beta, alpha, dense, m,
            var_max_iters=var_max_iters, var_tol=var_tol,
            interpret=jax.default_backend() != "tpu",
            wmajor=dense_wmajor,
            gamma_prev=g_in, warm=warm, precision=dense_precision,
            batch_index=batch_index,
        )

    dense_fn = dense_e_step_fn or _default_dense

    def _compact_dense(log_beta, alpha, dense_local, m, vocab_map, g_in,
                       warm):
        """Compact-vocab dense E-step (plan_compact): run the dense
        kernel over the batch's own Wc-wide vocabulary slice, then
        scatter the suff-stats rows back to the full [V, K] layout the
        M-step consumes.  Sentinel columns (vocab_map padding repeats
        word 0) carry zero local counts, so their suff-stats are
        exactly zero and the duplicate-index .add() is a no-op."""
        from ..ops import dense_estep

        beta_local = jnp.take(log_beta, vocab_map, axis=1)
        res = dense_estep.e_step_dense(
            beta_local, alpha, dense_local, m,
            var_max_iters=var_max_iters, var_tol=var_tol,
            interpret=jax.default_backend() != "tpu",
            wmajor=dense_wmajor,
            gamma_prev=g_in, warm=warm, precision=dense_precision,
        )
        ss = jnp.zeros((v, k), log_beta.dtype).at[vocab_map].add(
            res.suff_stats
        )
        return res._replace(suff_stats=ss)

    def accumulate(log_beta, alpha, groups, gammas_prev, warm):
        dtype = log_beta.dtype
        total_ss = jnp.zeros((v, k), dtype)
        total_ll = jnp.zeros((), dtype)
        total_ass = jnp.zeros((), dtype)
        vi_max = jnp.zeros((), jnp.int32)
        sweeps = jnp.zeros((), jnp.int32)
        gammas = []

        def run_batch(batch, g_in):
            if len(batch) == 2:                # dense group: (C [B,V], mask)
                return dense_fn(log_beta, alpha, *batch, g_in, warm)
            if jnp.issubdtype(batch[0].dtype, jnp.floating):
                # compact-dense group: (C_local, mask, vocab_map) —
                # disjoint from sparse, whose leading word_idx is
                # integer (dtype is static at trace time).
                return _compact_dense(log_beta, alpha, *batch, g_in, warm)
            w, c, m = batch                    # sparse group: (w, c, mask)
            if e_warm:
                return e_fn(
                    log_beta, alpha, w, c, m,
                    var_max_iters=var_max_iters, var_tol=var_tol,
                    gamma_prev=g_in, warm=warm,
                )
            return e_fn(
                log_beta, alpha, w, c, m,
                var_max_iters=var_max_iters, var_tol=var_tol,
            )

        def add(carry, res):
            ss, ll, ass, vi, sw = carry
            return (
                (ss + res.suff_stats, ll + res.likelihood,
                 ass + res.alpha_ss,
                 jnp.maximum(vi, jnp.asarray(res.vi_iters, jnp.int32)),
                 sw + jnp.asarray(res.doc_sweeps, jnp.int32)),
                res.gamma,
            )

        def scan_body(carry, batch_and_gamma):
            batch, g_in = batch_and_gamma
            return add(carry, run_batch(batch, g_in))

        carry = (total_ss, total_ll, total_ass, vi_max, sweeps)
        with jax.named_scope("estep"):
            for group, g_prev in zip(groups, gammas_prev):
                if group[0].shape[0] == 1:
                    # Single-batch group (the common case after
                    # bucketing): call the E-step directly instead of a
                    # length-1 lax.scan, whose slice-in/stack-out
                    # machinery adds fixed per-EM-iteration ops inside
                    # the chunk loop.
                    carry, g = scan_body(
                        carry, (tuple(a[0] for a in group), g_prev[0])
                    )
                    gammas.append(g[None])
                    continue
                body, xs = scan_body, (group, g_prev)
                if reads_stack_in_place(group, dense_e_step_fn):
                    # The stack stays whole, closed over and invariant in
                    # the scan, which carries the batch's number: the
                    # kernel reads batch `n` of the stack in place.
                    # Scanned over, XLA copied every batch out of the stack
                    # for the kernel's operand, once an EM iteration
                    # (`dynamic-slice_bitcast_fusion`: a quarter of the
                    # device's time, PERF.md PR 37).
                    stack, masks = group

                    def body(carry, index_mask_gamma):
                        n, m, g_in = index_mask_gamma
                        return add(carry, dense_fn(
                            log_beta, alpha, stack, m, g_in, warm,
                            batch_index=n))

                    xs = (jnp.arange(stack.shape[0], dtype=jnp.int32),
                          masks, g_prev)
                carry, g = jax.lax.scan(body, carry, xs)
                gammas.append(g)
        total_ss, total_ll, total_ass, vi_max, sweeps = carry
        return total_ss, total_ll, total_ass, tuple(gammas), vi_max, sweeps

    return accumulate


def make_partial_runner(*, compiler_options: dict | None = None, **kw):
    """The distributed driver's per-shard E-step program: one jitted
    call of the accumulator above, emitting the partial suff-stats /
    ELBO / alpha-ss for ONE document shard so the explicit allreduce
    (parallel/allreduce.py) can combine them across processes between
    the E and M steps.  `warm` is a traced scalar, so warm-start
    toggling never retraces."""
    acc = make_em_accumulator(**kw)
    return jax.jit(acc, compiler_options=compiler_options)


class ChunkResult(NamedTuple):
    log_beta: jax.Array
    alpha: jax.Array
    ll_prev: jax.Array          # scalar; nan before the first EM iteration
    lls: jax.Array              # [chunk] likelihood per executed step
    steps_done: jax.Array       # int32 scalar in [0, n_steps]
    converged: jax.Array        # bool scalar
    gammas: tuple               # per group: [NB, B, K] from the final E-step
    vi_iters: jax.Array         # [chunk] max inner fixed-point iterations
                                # per executed EM step (observability:
                                # shows the var_tol early exit + warm
                                # start collapsing the inner loop)
    doc_sweeps: jax.Array       # [chunk] int32 document-sweeps the E-step
                                # ran per executed EM step: the sum over
                                # batches of EStepResult.doc_sweeps


# -- the chunk program, kept across fits -------------------------------------

# Plan-cache knobs the kernels' block picks look up while the chunk program
# is traced (dense_estep.pick_block / pick_block_w, sparse_estep.pick_block).
_TRACED_PLAN_KNOBS = frozenset(
    ("dense_estep_block", "dense_estep_block_w", "sparse_estep_bb"))

_PROGRAMS_MAX = 8
_PROGRAMS: "OrderedDict[tuple, Callable]" = OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def _traced_plan_blocks() -> tuple:
    """The active plan store's entries for `_TRACED_PLAN_KNOBS`: what the
    kernels' block picks can read at trace time (a multi-host run reads
    none: dense_estep._planned_block)."""
    from .. import plans

    try:
        store = plans.current_store() if jax.process_count() == 1 else None
        entries = store.entries() if store is not None else ()
    except Exception:   # an unreadable store reads as empty: lookup_value
        entries = ()
    return tuple(sorted(
        (e.knob, e.backend, e.shape, e.value) for e in entries
        if e.knob in _TRACED_PLAN_KNOBS))


def _program_key(traced: dict) -> "tuple | None":
    """The chunk program's identity, from everything its trace reads.

    Closure values (`traced`, the arguments of `_build_chunk_program`):
    the scalars with their types, `compiler_options` as sorted items, the
    three step callables by identity (the program closes over them, so an
    id cannot be reused while its entry lives).  That tells meshes apart
    too: the makers of parallel/sharded.py hand out one object per mesh
    and settings, so two meshes are two programs and a second fit on a
    mesh reuses the first one's.  Read at trace time from
    outside the arguments: `ONI_ML_TPU_ESTEP` (estep.resolve_backend),
    `jax.default_backend()` (the kernels' interpret flag and the engine
    gates) and the plan cache's kernel blocks (`_traced_plan_blocks`,
    which covers `ONI_ML_TPU_PLANS`, `ONI_ML_TPU_PLAN_CACHE` and
    `plans.use_store`).  jax's own configuration (x64, matmul precision)
    is `jax.jit`'s to key, inside the kept function; the groups' shapes
    and dtypes are its operands.  `ONI_ML_TPU_ESTEP_ENGINE` and the
    engine crossover are read by the driver on the host, before it
    chooses these arguments.  What a trace only SAYS is not keyed: the
    `estep_dispatch` log line and journal record (estep._report_dispatch)
    appear when a program is built, not when it is reused.

    None when a value cannot be hashed: the caller builds a fresh
    program, as every call did before programs were kept."""
    fns = ("e_step_fn", "m_step_fn", "dense_e_step_fn")
    options = traced["compiler_options"]
    key = (
        tuple((name, type(val), val) for name, val in sorted(traced.items())
              if name not in fns and name != "compiler_options"),
        None if options is None else tuple(sorted(options.items())),
        tuple(id(traced[name]) for name in fns),
        os.environ.get("ONI_ML_TPU_ESTEP", "auto"),
        jax.default_backend(),
        _traced_plan_blocks(),
    )
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _chunk_program(traced: dict) -> "tuple[Callable, bool]":
    """(the jitted chunk program for `traced`, whether this process had
    built it already).  A small LRU under a lock: fits on threads (the
    refresh worker, a co-scheduled fit) asking for one program at once
    build one.  It holds `jax.jit` objects and the Python they close over
    and nothing of a fit: no array, batch or trainer.  An evicted entry
    drops its `jax.jit`, and its executables go with it."""
    key = _program_key(traced)
    if key is None:
        return _build_chunk_program(**traced), False
    with _PROGRAMS_LOCK:
        jitted = _PROGRAMS.get(key)
        if jitted is not None:
            _PROGRAMS.move_to_end(key)
            return jitted, True
        jitted = _PROGRAMS[key] = _build_chunk_program(**traced)
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
    return jitted, False


def clear_programs() -> None:
    """Forget every kept chunk program: the next fit builds its own, as a
    new process would."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def _build_chunk_program(
    *,
    num_docs: int,
    num_topics: int,
    num_terms: int,
    chunk: int,
    var_max_iters: int,
    var_tol: float,
    em_tol: float,
    estimate_alpha: bool,
    e_step_fn: Callable,
    m_step_fn: Callable,
    compiler_options: dict | None,
    dense_wmajor: bool,
    warm_start: bool,
    dense_e_step_fn: Callable | None,
    dense_precision: str,
    alpha_max_iters: int,
):
    """The PROGRAM half of `make_chunk_runner`: `jax.jit` of
    `run_chunk_dispatch(log_beta, alpha, ll_prev, groups, n_steps,
    gammas_in, have_prev) -> ChunkResult` and everything it closes over.
    Every argument is read by the trace and is part of `_program_key`; an
    argument added here is keyed without further ado, a read of anything
    else at trace time has to be added to the key by hand."""
    from .lda import update_alpha  # local import: lda.py imports this module

    k = num_topics
    # The E-step callable itself now lives inside the accumulator (the
    # shared partial-stats path the distributed driver also jits).
    accumulate = make_em_accumulator(
        num_topics=num_topics, num_terms=num_terms,
        var_max_iters=var_max_iters, var_tol=var_tol,
        e_step_fn=e_step_fn, dense_e_step_fn=dense_e_step_fn,
        dense_wmajor=dense_wmajor, dense_precision=dense_precision,
        warm_start=warm_start,
    )

    def em_iteration(log_beta, alpha, groups, gammas_prev, warm):
        total_ss, total_ll, total_ass, gammas, vi_max, sweeps = accumulate(
            log_beta, alpha, groups, gammas_prev, warm
        )
        with jax.named_scope("mstep"):
            new_beta = m_step_fn(total_ss)
        new_alpha = (
            update_alpha(total_ass, alpha, num_docs, k,
                         max_iters=alpha_max_iters)
            if estimate_alpha
            else alpha
        )
        return new_beta, new_alpha, total_ll, tuple(gammas), vi_max, sweeps

    def run_chunk_dispatch(log_beta, alpha, ll_prev, groups, n_steps,
                           gammas_in=None, have_prev=None) -> ChunkResult:
        """The chunk while-loop: warm gating, the device convergence
        rule, and the step/ll/vi bookkeeping around `em_iteration`.  The
        benchmark finds the EM program in a trace by this function's
        name (`jit_run_chunk_dispatch`), and the name is part of jax's
        compile-cache key."""
        dtype = log_beta.dtype
        # Gamma buffers must exist in the carry before the first
        # iteration writes them.  `gammas_in`/`have_prev` carry the
        # PREVIOUS chunk's posteriors across the host boundary so warm
        # start survives chunk boundaries (without them iteration
        # chunk*i+1 restarted fresh); when absent, zeros are never read
        # back (warm gates on step>0).
        if gammas_in is None:
            gammas0 = initial_gammas(groups, k, dtype,
                                     dense_wmajor=dense_wmajor)
            have_prev = jnp.asarray(False)
        else:
            gammas0, have_prev = gammas_in, jnp.asarray(have_prev)
        lls0 = jnp.zeros((chunk,), dtype)
        vi0 = jnp.zeros((chunk,), jnp.int32)

        def cond(state):
            _, _, _, step, _, _, _, converged, _ = state
            return (step < jnp.minimum(n_steps, chunk)) & ~converged

        def body(state):
            (log_beta, alpha, ll_prev, step, lls, vis, sws, _,
             gammas_prev) = state
            # Warm start once ANY gamma exists: produced this chunk
            # (step>0) or carried in from the previous one (have_prev).
            warm = (
                (step > 0) | have_prev
                if warm_start
                else jnp.asarray(False)
            )
            log_beta, new_alpha, ll, gammas, vi, sweeps = em_iteration(
                log_beta, alpha, groups, gammas_prev, warm
            )
            # The first-ever iteration (ll_prev = nan) never stops — the
            # reference's "no previous likelihood" case.  The host
            # recomputes logged convergence values in float64 from the
            # returned lls.
            conv = jnp.abs((ll_prev - ll) / ll_prev)
            converged = ~jnp.isnan(ll_prev) & (conv < em_tol)
            return (
                log_beta,
                new_alpha,
                ll,
                step + 1,
                lls.at[step].set(ll),
                vis.at[step].set(jnp.asarray(vi, jnp.int32)),
                sws.at[step].set(jnp.asarray(sweeps, jnp.int32)),
                converged,
                gammas,
            )

        state = (
            log_beta, alpha, ll_prev, jnp.asarray(0, jnp.int32),
            lls0, vi0, vi0, jnp.asarray(False), gammas0,
        )
        log_beta, alpha, ll_prev, step, lls, vis, sws, converged, gammas = (
            jax.lax.while_loop(cond, body, state)
        )
        return ChunkResult(
            log_beta, alpha, ll_prev, lls, step, converged, gammas, vis, sws
        )

    return jax.jit(run_chunk_dispatch, compiler_options=compiler_options)


def make_chunk_runner(
    *,
    num_docs: int,
    num_topics: int,
    num_terms: int,
    chunk: int,
    var_max_iters: int,
    var_tol: float,
    em_tol: float,
    estimate_alpha: bool,
    e_step_fn: Callable | None = None,
    m_step_fn: Callable | None = None,
    compiler_options: dict | None = None,
    dense_wmajor: bool = False,
    warm_start: bool = False,
    dense_e_step_fn: Callable | None = None,
    dense_precision: str = "f32",
    alpha_max_iters: int = 100,
    yield_hook: Callable | None = None,
):
    """Build `run_chunk(log_beta, alpha, ll_prev, groups, n_steps)`
    executing up to min(chunk, n_steps) EM iterations on device.

    Two halves.  The PROGRAM (`_build_chunk_program`) is the `jax.jit` of
    the one chunk loop, `run_chunk_dispatch`, whatever the groups' layout
    (dense, compact or token lists; one batch or many); a process builds
    it once per distinct program
    (`_chunk_program`), so a later fit that asks for the same one
    dispatches the executable the earlier fit traced and compiled, and
    its first dispatch is an enqueue like the others.  The RUNNER, built
    here on every call, is the light host wrapper around it: spans, the
    preemption slot, the roofline harvest.  `runner.program` says which
    it got: "reused" or "built".

    `n_steps` is a traced scalar, so checkpoint boundaries and the final
    partial chunk reuse the single compiled program.

    `yield_hook` (a context-manager factory, e.g.
    `serving.CoScheduler.train_chunk`) makes each chunk dispatch
    PREEMPTIBLE: the runner enters one hook slot per dispatch, so a
    co-resident serving plane wins the next dispatch slot at every
    chunk boundary — the fused chunk is the natural preemption grain.
    """
    e_fn = e_step_fn or estep.e_step
    m_fn = m_step_fn or estep.m_step
    jitted, reused = _chunk_program(dict(
        num_docs=num_docs, num_topics=num_topics, num_terms=num_terms,
        chunk=chunk, var_max_iters=var_max_iters, var_tol=var_tol,
        em_tol=em_tol, estimate_alpha=estimate_alpha, e_step_fn=e_fn,
        m_step_fn=m_fn, compiler_options=compiler_options,
        dense_wmajor=dense_wmajor, warm_start=warm_start,
        dense_e_step_fn=dense_e_step_fn, dense_precision=dense_precision,
        alpha_max_iters=alpha_max_iters,
    ))
    dispatch_no = itertools.count()

    def runner(log_beta, alpha, ll_prev, groups, n_steps, *args, **kw):
        """Host-side dispatch wrapper: each chunk dispatch is an
        `em.run_chunk` span (telemetry/spans.py: recorded under a
        Recorder, in the profiler's trace under a profiler session, a
        no-op otherwise).  JAX dispatch is asynchronous, so the span
        measures ENQUEUE — this runner's `first` dispatch also holds the
        chunk program's trace, lowering and cache fetch when the program
        was `built` for it (or meets new group shapes), the others the
        per-dispatch cost the chunked driver exists to amortize — not
        device compute; the driver's host-sync span covers the blocking
        side."""
        slot = yield_hook() if yield_hook is not None else nullcontext()
        with slot, maybe_span("em.run_chunk", chunk=chunk,
                              n_steps=int(n_steps)
                              if isinstance(n_steps, int) else None,
                              first=next(dispatch_no) == 0):
            out = jitted(log_beta, alpha, ll_prev, groups, n_steps,
                         *args, **kw)
        if current_recorder() is None:
            return out
        # Roofline harvest, once per shape, only under an active
        # recorder — AFTER the live dispatch, so the program is already
        # traced and in the persistent compilation cache: the AOT
        # lower+compile that reads XLA's per-dispatch FLOPs/bytes is a
        # cache hit, never a cold compile delaying first results.
        # (Safe post-dispatch: this jit donates nothing, so the
        # operands' shapes are still readable.)  Uninstrumented runs
        # never pay the extra trace.
        from ..telemetry import roofline

        roofline.ensure_harvested(
            "em.run_chunk", jitted, log_beta, alpha, ll_prev, groups,
            n_steps, *args, shape=f"chunk{chunk}", **kw,
        )
        return out

    # The EFFECTIVE dispatch settings ride on the runner so callers that
    # report them (bench.py's phase records) read what this runner was
    # actually built with — a monkeypatched maker (tools/tpu_probes.py
    # alpha_ab overrides alpha_max_iters inside its wrapper) would
    # otherwise desync the payload from the measurement.
    runner.alpha_max_iters = alpha_max_iters
    runner.chunk = chunk
    runner.jitted = jitted  # AOT access (tools/config4_hbm_probe.lower)
    runner.program = "reused" if reused else "built"
    return runner
