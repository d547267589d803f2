"""A day that one chip holds only at 2 B a cell (the benchmark's
`flow20_bigday_fit`, and `flow20_feedback_fit` with the analyst feedback's
cells of 1,000 and more): the plan budgets the full-width corpus at the
store's itemsize, decided by the gate's ladder (bfloat16 to 256, int16 to
32,767, float32) BEFORE the budget test; the densify holds one batch's
float32 block, never a float32 copy of a group.  At f32 the plan is what it
was.

The plan is driven with `on_tpu` stood in for (`jax.default_backend`
answers "tpu" for the length of the call): that is what lets `auto` take a
dense family at all.  Nothing is placed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from unittest import mock

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import Batch
from oni_ml_tpu.models import LDATrainer, fused
from oni_ml_tpu.ops import dense_estep
from oni_ml_tpu.telemetry import spans

V, K, B, L = 40, 4, 16, 8
W = dense_estep.padded_width(V)


def _batches(dup_cell=None, n=3, seed=0):
    """`n` batches of [B, L] count-1 tokens of distinct-enough words; with
    `dup_cell`, document 0 of the first batch is L tokens of ONE word whose
    counts sum to it (the DUPFACTOR feedback document's shape)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        w = rng.integers(0, V, (B, L)).astype(np.int32)
        c = np.ones((B, L), np.float32)
        if dup_cell is not None and i == 0:
            w[0] = 7
            c[0] = dup_cell / L
        out.append(Batch(word_idx=w, counts=c,
                         doc_index=np.arange(i * B, (i + 1) * B),
                         doc_mask=np.ones((B,), np.float32)))
    return out


def _sizes(batches):
    sparse = sum(b.word_idx.size * 8 for b in batches)
    return (fused.dense_groups_bytes(batches, V, itemsize=2) + sparse,
            fused.dense_groups_bytes(batches, V, itemsize=4) + sparse)


def _plan(batches, precision, budget):
    """The plan on a stood-in TPU, and the `fit.plan.gate` spans' args."""
    cfg = LDAConfig(num_topics=K, batch_size=B, dense_precision=precision,
                    dense_hbm_budget=budget)
    trainer = LDATrainer(cfg, num_terms=V)
    rec = spans.Recorder()
    with spans.use_recorder(rec), mock.patch.object(
            jax, "default_backend", lambda: "tpu"):
        plan = trainer._plan_estep(batches)
    gates = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    return plan, gates


def test_a_day_that_fits_only_at_2_bytes_a_cell_is_dense_and_stored_bf16():
    batches = _batches()
    two, four = _sizes(batches)
    plan, gates = _plan(batches, "bf16", (two + four) // 2)
    assert (plan.family, plan.kernel) == ("dense", "dense_wmajor")
    assert plan.store == jnp.bfloat16
    assert plan.cell_scan == "bounds" and plan.scan_tokens > 0
    # the gate ran once, in a span of its own, and says what it decided
    gate, = gates
    assert gate["store"] == "bfloat16"
    assert (gate["cell_scan"], gate["scan_tokens"]) == ("bounds",
                                                        plan.scan_tokens)
    assert (gate["undecided_rows"], gate["repeat_rows"]) == (0, 0)


def test_a_cell_over_256_keeps_float32_and_the_day_is_not_dense():
    """A cell over 256 that no 2-byte store holds exactly: 40,000, over
    int16's 32,767."""
    batches = _batches(dup_cell=40000)   # every raw count 5,000
    two, four = _sizes(batches)
    plan, gates = _plan(batches, "bf16", (two + four) // 2)
    assert plan.family != "dense"
    assert plan.store == jnp.float32
    gate, = gates
    assert (gate["store"], gate["cell_scan"], gate["rung"]) == (
        "float32", "exact", "f32")
    # document 0, one word L times, is the one row over 32,767 and repeats
    assert (gate["undecided_rows"], gate["repeat_rows"]) == (1, 1)
    assert gate["over_bf16_tokens"] == L


@pytest.mark.parametrize("cell, over", [(257, 0), (2048, 0), (32767, L)])
def test_a_feedback_cell_a_2_byte_int16_store_holds_keeps_the_day_dense(
        cell, over):
    """The analyst-feedback day: a cell over 256 that int16 holds exactly
    (as summed count-1 tokens, or as raw counts over 256) is stored int16,
    and the day that fits only at 2 B a cell stays full-width dense."""
    batches = _batches(dup_cell=cell)     # L raw counts of cell / L
    assert int((batches[0].counts > 256).sum()) == over
    two, four = _sizes(batches)
    plan, gates = _plan(batches, "bf16", (two + four) // 2)
    assert (plan.family, plan.kernel) == ("dense", "dense_wmajor")
    assert plan.store == jnp.int16
    gate, = gates
    assert (gate["store"], gate["rung"]) == ("int16", "int16")
    assert gate["over_bf16_tokens"] == over
    # under a raw count over 256 the bound is int16's, which no row passes
    assert gate["cell_scan"] == ("bounds" if over else "exact")
    assert (gate["undecided_rows"], gate["repeat_rows"]) == (
        (0, 0) if over else (1, 1))


# budget -> (family, kernel, store, cell_scan, scan_tokens), as the parent
# of PR 41 plans them: at f32 the gate reads no token and nothing moved.
F32_PLANS = {
    "roomy": ("dense", "dense_wmajor", jnp.float32, "none", 0),
    "between": ("tokens", "sparse", jnp.float32, "none", 0),
}


@pytest.mark.parametrize("budget", sorted(F32_PLANS))
def test_at_f32_the_plan_is_what_it_was(budget):
    batches = _batches()
    two, four = _sizes(batches)
    plan, _ = _plan(batches, "f32",
                    four * 4 if budget == "roomy" else (two + four) // 2)
    assert (plan.family, plan.kernel, plan.store, plan.cell_scan,
            plan.scan_tokens) == F32_PLANS[budget]


def _recorded_densify(batches, dtype, wmajor):
    groups = fused.stack_batches(batches, np.float32, jnp.asarray)
    fused.densify_stack.clear_cache()     # so that its program is built
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        dense = fused.densify_groups(groups, V, wmajor=wmajor, dtype=dtype)
    args, = [e["args"] for e in rec.events if e["name"] == "fit.densify"]
    return groups, dense, args


@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_densify_into_bf16_is_the_float32_densify_cast(wmajor):
    """Duplicates that sum to exactly 256, the most bfloat16 holds exactly,
    beside a raw count of 256."""
    batches = _batches(dup_cell=256)
    batches[1].word_idx[3] = np.arange(L)
    batches[1].counts[3, 0] = 256.0
    groups, dense, args = _recorded_densify(batches, jnp.bfloat16, wmajor)
    (widx, cnts, _), = groups.arrays
    want = jax.vmap(lambda w, c: dense_estep.densify(w, c, V))(widx, cnts)
    if wmajor:
        want = jnp.swapaxes(want, 1, 2)
    (got, _), = dense.arrays
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(jnp.bfloat16),
                                             np.float32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want))
    first = np.asarray(got[0], np.float32)
    assert (first[7, 0] if wmajor else first[0, 7]) == 256.0
    # the span says the store's width and, as the program was built, its
    # scratch: under one float32 group
    assert args["itemsize"] == 2 and args["program"] == "built"
    assert 0 < args["temp_bytes"] < got.shape[0] * B * W * 4
    assert args["dense_bytes"] == got.size * 2


@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_the_compiled_narrow_densify_holds_no_float32_group(wmajor):
    nb, rows, length, width = 8, 64, 16, 512
    sparse = [jax.ShapeDtypeStruct((nb, rows, length), dt)
              for dt in (jnp.int32, jnp.float32)]
    compiled = fused.densify_stack.lower(
        *sparse, num_terms=width, width=None, dtype=jnp.bfloat16,
        wmajor=wmajor).compile()
    mem = compiled.memory_analysis()
    group_f32 = nb * rows * width * 4
    assert mem.output_size_in_bytes == group_f32 // 2
    # one batch's float32 block and its cast, not the group's (the CPU
    # backend reports its scratch; the chip's compiler is held at the big
    # day's size in tests/test_chip_compile.py)
    assert mem.temp_size_in_bytes <= 2 * rows * width * 4 < group_f32
