"""The products are keyed to what the day's cells hold, not to the store's
dtype: wherever a densified cell of the fit's day passes 256 (the day after
analyst feedback, DUPFACTOR=1000), every product keeps float32 accuracy on
the default float32 store too, as on the int16 store; anywhere else XLA's
DEFAULT, as before.  At f32 the gate (`dense_estep.accurate_products`)
reads the largest count of the fit's Corpus, whose entries are its cells,
or, over batches from no Corpus, climbs the store ladder's first rung
alone, under its exactness rules.  Under those products the fixed point
drops the stall exit, on every store.  Plans are made with `on_tpu` stood
in for (`jax.default_backend` answers "tpu" for the length of the call);
kernels run interpreted.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from unittest import mock

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io import Batch, Corpus
from oni_ml_tpu.models import LDATrainer, fused, train_corpus
from oni_ml_tpu.ops import dense_estep
from oni_ml_tpu.telemetry import spans

V, K, B, L = 40, 4, 16, 8

# case -> whether a densified cell of the day passes 256
CASES = {
    "flow20": False,              # distinct words, counts 1-3
    "raw_256": False,             # bfloat16 holds 256 exactly
    "raw_257": True,
    "repeat_sums_320": True,      # one word, eight tokens of 40
    "repeat_sums_256": False,     # one word, eight tokens of 32
    "distinct_row_320": False,    # a row over 256, no word twice
}


def _batch(w, c):
    w = np.asarray(w, np.int32)
    return Batch(word_idx=w, counts=np.asarray(c, np.float32),
                 doc_index=np.arange(w.shape[0]),
                 doc_mask=np.ones((w.shape[0],), np.float32))


def _day(case, seed=0):
    """Two batches of [B, L] tokens of distinct words at counts 1-3, and in
    the second batch's document 0 what the case names."""
    rng = np.random.default_rng(seed)
    days = [(np.argsort(rng.random((B, V)), axis=1)[:, :L],
             rng.integers(1, 4, (B, L)).astype(np.float32))
            for _ in range(2)]
    w, c = days[1]
    if case.startswith("raw_"):
        c[0, 3] = float(case[4:])
    elif case.startswith("repeat_sums_"):
        w[0], c[0] = 5, float(case[12:]) / L
    elif case == "distinct_row_320":
        c[0] = 40.0
    return [_batch(w, c) for w, c in days]


def _cell_max(batches):
    return max(dense_estep.max_dense_cell(b.word_idx, b.counts)
               for b in batches)


def _plan(batches, precision="f32", cells=None):
    """The plan on a stood-in TPU, and the `fit.plan.gate` spans' args."""
    cfg = LDAConfig(num_topics=K, batch_size=B, dense_precision=precision,
                    dense_hbm_budget=2**30)
    trainer = LDATrainer(cfg, num_terms=V, corpus_counts=cells)
    rec = spans.Recorder()
    with spans.use_recorder(rec), mock.patch.object(
            jax, "default_backend", lambda: "tpu"):
        plan = trainer._plan_estep(batches)
    gates = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    return plan, gates


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_f32_plan_takes_accurate_products_where_a_cell_passes_256(case):
    """At f32 the store is float32 whatever the cells hold, and the plan's
    products follow the cells: packed where a densified cell passes 256
    (raw, or summed from repeated tokens), XLA's DEFAULT where none does,
    a row over 256 of distinct words included."""
    batches = _day(case)
    want = CASES[case]
    assert (_cell_max(batches) > 256) == want
    plan, gates = _plan(batches)
    assert (plan.family, plan.kernel, plan.store) == (
        "dense", "dense_wmajor", jnp.float32)
    assert (plan.cell_scan, plan.scan_tokens) == ("none", 0)
    assert plan.accurate == want
    gate, = gates
    assert (gate["store"], gate["rung"], gate["accurate"]) == (
        "float32", "f32", int(want))
    assert dense_estep.kernel_products(plan.store, K, plan.accurate) == (
        "split6_packed" if want else "default")
    # bf16 operand mode stores the same day by the ladder, and its rung
    # gives the same answer without a pass of its own
    bf16, (gate,) = _plan(batches, "bf16")
    assert bf16.accurate == want
    assert bf16.store == (jnp.int16 if want else jnp.bfloat16)
    assert gate["accurate"] == int(want)


def test_accurate_products_is_the_exact_reader_on_random_batches():
    """Random batches with repeats planted around the 256 line, rows of
    distinct words and zero-count padding tails: at f32 the answer is
    whether the exact reader's largest cell passes 256; under bf16 it is
    the store's rung above bfloat16."""
    rng = np.random.default_rng(257)
    seen = set()
    for _ in range(300):
        b, l, v = rng.integers(1, 9), rng.integers(1, 40), rng.integers(1, 50)
        top = int(rng.choice([1, 3, 40, 256, 257, 300]))
        w = rng.integers(0, v, (b, l))
        if l <= v and rng.random() < 0.5:
            w = np.argsort(rng.random((b, v)), axis=1)[:, :l]
        c = rng.integers(0, top + 1, (b, l)).astype(np.float64)
        for _ in range(rng.integers(0, 4)):
            row, n = rng.integers(0, b), rng.integers(1, l + 1)
            w[row, :n] = w[row, 0]
            c[row, :n] = rng.choice([1.0, 256.0 / n, 300.0 // n + 1])
        pad = rng.integers(0, 9)
        w = np.concatenate([w, np.zeros((b, pad), int)], axis=1)
        c = np.concatenate([c, np.zeros((b, pad))], axis=1)
        batches = [_batch(w, np.floor(c))]
        want = _cell_max(batches) > 256
        got = dense_estep.accurate_products(jnp.float32, "f32", batches)
        assert got == want, (w, c)
        store, _, _ = dense_estep.corpus_store_dtype(batches, "bf16")
        assert dense_estep.accurate_products(store, "bf16", batches) == want
        seen.add(want)
    assert seen == {False, True}


class _Counts:
    def annotate(self, **kw):
        self.__dict__.update(kw)


# case -> (a Corpus' counts, whether one passes 256)
CORPUS_COUNTS = {
    "flow20": (np.array([1, 3, 2, 4], np.int32), False),
    "count_256": (np.array([1, 256, 3], np.int32), False),
    "count_257": (np.array([257, 1, 3], np.int32), True),
    "feedback": (np.array([2, 3001, 1, 1000, 12001], np.int32), True),
    "empty": (np.zeros(0, np.int32), False),
}


@pytest.mark.parametrize("case", sorted(CORPUS_COUNTS))
def test_at_f32_a_corpus_counts_decide_in_one_pass(case, monkeypatch):
    """Where the fit hands the gate its Corpus' counts, one entry a
    (document, word) cell, their largest decides at f32, whatever the
    batches hold, and the ladder over the batches is not climbed; the
    span says the largest and counts the entries read."""
    counts, want = CORPUS_COUNTS[case]

    def unread(*a, **kw):
        raise AssertionError("the gate read the batches")

    monkeypatch.setattr(dense_estep, "_ladder_gate", unread)
    span = _Counts()
    batches = _day("repeat_sums_320")
    assert dense_estep.accurate_products(jnp.float32, "f32", batches,
                                         span=span, cells=counts) is want
    assert span.accurate_scan_tokens == counts.size
    assert span.largest_count == counts.max(initial=0)
    assert not hasattr(span, "undecided_rows")
    plan, (gate,) = _plan(batches, cells=counts)
    assert (plan.accurate, gate["accurate"]) == (want, int(want))
    assert dense_estep.kernel_products(plan.store, K, plan.accurate) == (
        "split6_packed" if want else "default")


@pytest.mark.parametrize("size", [0, 1, 7, 8, 33])
def test_the_largest_count_is_numpys_in_slices_or_not(size, monkeypatch):
    """`_largest` reads a large array in slices on threads of their own
    (slices of at least 2 counts here), a small one in one pass: either
    way the largest count, 0 where there is none."""
    monkeypatch.setattr(dense_estep, "_LARGEST_SLICE_MIN", 2)
    counts = np.random.default_rng(size).integers(0, 1000, size)
    want = float(counts.max(initial=0))
    assert dense_estep._largest(counts) == want
    assert dense_estep._largest(counts.astype(np.float32)) == want


def _tiny_corpus(feedback: bool, num_docs=48, seed=3):
    """Documents of 4-12 distinct words at counts 1-3; with `feedback`,
    document 0 holds one word at a count of 300."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 13, num_docs)
    words = [rng.choice(V, n, replace=False) for n in lens]
    counts = [rng.integers(1, 4, n).astype(np.float32) for n in lens]
    if feedback:
        lens[0], words[0], counts[0] = 1, np.full(1, 5), np.full(1, 300.0)
    return Corpus(doc_names=[f"ip{d}" for d in range(num_docs)],
                  vocab=[f"w{i}" for i in range(V)],
                  doc_ptr=np.concatenate([[0], np.cumsum(lens)]),
                  word_idx=np.concatenate(words).astype(np.int32),
                  counts=np.concatenate(counts).astype(np.float32))


def test_two_fits_that_differ_only_in_accurate_share_no_chunk_program():
    """The kept chunk program is keyed by `dense_accurate` like every
    argument it traces with: a float32 day with a cell over 256 and one
    without build a program each, and each is found again by its own
    kind of day."""
    fused.clear_programs()
    cfg = LDAConfig(num_topics=K, batch_size=16, em_max_iters=2, em_tol=0.0,
                    seed=1, estep_engine="dense")
    programs, products = [], []
    for feedback in (True, False, True, False):
        rec = spans.Recorder()
        with spans.use_recorder(rec):
            res = train_corpus(_tiny_corpus(feedback), cfg)
        programs += [e["args"]["program"] for e in rec.events
                     if e["name"] == "fit.runner"]
        products.append(res.plan["products"]["value"])
    assert programs == ["built", "built", "reused", "reused"]
    assert products == ["split6_packed", "default"] * 2
    keys = [fused._program_key(dict(_TRACED, dense_accurate=a))
            for a in (False, True)]
    assert keys[0] != keys[1]


_TRACED = dict(num_docs=8, num_topics=K, num_terms=V, chunk=2,
               var_max_iters=4, var_tol=1e-6, em_tol=0.0,
               estimate_alpha=True, e_step_fn=None, m_step_fn=None,
               compiler_options=None, dense_wmajor=True, warm_start=True,
               dense_e_step_fn=None, dense_precision="f32",
               alpha_max_iters=8)


def _dots(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_dots(inner))
    return out


def _highest(dot) -> bool:
    return dot.params["precision"] not in (None, (None, None))


@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_a_float32_blocks_kernel_body_packs_its_products_where_accurate(
        wmajor):
    """`accurate` over a float32 block gives the body the int16 block's
    products: bfloat16 pieces, and one HIGHEST product, the tail's
    statistics; without it, the four DEFAULT products it had."""
    fn = dense_estep._block_e_step_w if wmajor else dense_estep._block_e_step
    k, w, bb = 20, 256, 128
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((), f32), jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((k, w), f32),
            jax.ShapeDtypeStruct((w, bb) if wmajor else (bb, w), f32),
            jax.ShapeDtypeStruct((1, bb) if wmajor else (bb, 1), f32),
            jax.ShapeDtypeStruct((k, bb) if wmajor else (bb, k), f32))

    def dots(accurate):
        return _dots(jax.make_jaxpr(lambda *a: fn(
            *a, var_max_iters=20, var_tol=1e-6, precision="f32",
            accurate=accurate))(*args).jaxpr)

    packed, plain = dots(True), dots(False)
    assert len(plain) == 4 and not any(map(_highest, plain))
    highest = [d for d in packed if _highest(d)]
    assert len(packed) > 4 and len(highest) == 1
    assert {str(v.aval.dtype) for d in packed if d not in highest
            for v in d.invars} == {"bfloat16"}


@pytest.mark.parametrize("wmajor", [False, True], ids=["rowmajor", "wmajor"])
def test_the_float32_store_under_accurate_is_the_int16_stores_kernel(wmajor):
    """e_step_dense over a float32 block with `accurate` and over the int16
    block of the same counts: the same products on the same promoted
    values, the same numbers bit for bit."""
    rng = np.random.default_rng(48)
    b, length, v = 128, 16, 256
    w = np.argsort(rng.random((b, v)), axis=1)[:, :length].astype(np.int32)
    c = rng.integers(1, 4, (b, length)).astype(np.float32)
    c[::9, 0] = 3000.0
    log_beta = jnp.log(jnp.asarray(rng.dirichlet(np.ones(v), size=K),
                                   jnp.float32))
    mask = jnp.ones((b,), jnp.float32)
    got = {}
    for dtype, precision, accurate in ((jnp.float32, "f32", True),
                                       (jnp.int16, "bf16", False)):
        dense = dense_estep.densify(jnp.asarray(w), jnp.asarray(c), v,
                                    dtype=dtype)
        got[dtype] = dense_estep.e_step_dense(
            log_beta, jnp.float32(2.5), dense.T if wmajor else dense, mask,
            var_max_iters=12, var_tol=1e-5, interpret=True, wmajor=wmajor,
            precision=precision, accurate=accurate)
    for field in got[jnp.int16]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got[jnp.float32], field)),
            np.asarray(getattr(got[jnp.int16], field)), err_msg=field)


def test_the_vocab_sharded_plan_takes_highest_where_accurate():
    """The XLA-level vocab-sharded dense E-step packs nothing: with
    `accurate` on a float32 store every product is at HIGHEST on float32
    operands, without it at DEFAULT."""
    from oni_ml_tpu.parallel import make_mesh, make_vocab_sharded_dense_e_step

    mesh = make_mesh(data=2, model=4)
    b, v = 16, 256
    f32 = jnp.float32
    for accurate in (True, False):
        fn = make_vocab_sharded_dense_e_step(mesh, precision="f32",
                                             accurate=accurate)
        closed = jax.make_jaxpr(lambda lb, a, d, m, g: fn(
            lb, a, d, m, g, jnp.asarray(0), var_max_iters=5,
            var_tol=1e-6))(
            jax.ShapeDtypeStruct((K, v), f32), jax.ShapeDtypeStruct((), f32),
            jax.ShapeDtypeStruct((b, v), f32), jax.ShapeDtypeStruct((b,), f32),
            jax.ShapeDtypeStruct((b, K), f32))
        dots = _dots(closed.jaxpr)
        assert len(dots) >= 3
        assert all(_highest(d) == accurate for d in dots)


def test_the_feedback_days_blocks_keep_their_doc_block_at_f32():
    """The VMEM model counts the packed products' operands over a float32
    block under `accurate` as it does over an int16 one: the feedback
    day's [4096, 8192] batches keep a doc block of 256 in either layout,
    and flow20's tails (3,968 and 3,456 rows) W-major blocks of 128."""
    b, v, k, f32 = 4096, 8192, 20, jnp.float32
    assert dense_estep._vmem_estimate(256, v, k, "f32", f32, True) == (
        dense_estep._vmem_estimate(256, v, k, "bf16", jnp.int16))
    assert dense_estep._vmem_estimate(256, v, k, "f32", f32) == (
        dense_estep._vmem_estimate(256, v, k, "f32"))
    for pick in (dense_estep.pick_block, dense_estep.pick_block_w):
        assert pick(b, v, k, "f32", f32, True) == 256
    for rows in (3968, 3456):
        assert dense_estep.pick_block_w(rows, v, k, "f32", f32, True) == 128
    assert dense_estep.scoped_vmem_kib(
        b, v, k, wmajor=True, store=f32, accurate=True
    ) > dense_estep.scoped_vmem_kib(b, v, k, wmajor=True, store=f32)


@pytest.mark.parametrize("dtype, accurate, stall", [
    (jnp.float32, True, False), (jnp.float32, False, True),
    (jnp.int16, True, False), (jnp.int16, False, False),
    (jnp.bfloat16, False, True)])
def test_the_stall_exit_stays_only_under_default_products(
        dtype, accurate, stall):
    """One rule for every store: wherever the products keep float32
    accuracy (a day with a cell over 256, and always an int16 block) the
    fixed point stops at var_tol or var_max_iters alone, the reference's
    rule; under XLA's DEFAULT it keeps the stagnation exit."""
    assert dense_estep._stall_exit(dtype, accurate) is stall
    assert stall is (dense_estep.kernel_products(dtype, K, accurate)
                     == "default")


@pytest.mark.parametrize("dtype, accurate", [
    (jnp.float32, True), (jnp.int16, False)], ids=["float32", "int16"])
def test_past_k_106_the_highest_products_keep_no_stall_exit_either(
        dtype, accurate):
    """Where K is too wide to pack (HIGHEST's six passes or fewer), the
    products still keep float32 accuracy, and the fixed point still
    follows the reference's rule."""
    for k in (106, 107, 200):
        products = dense_estep.kernel_products(dtype, k, accurate)
        assert products == ("split6_packed" if k <= 106 else "highest")
    assert dense_estep._stall_exit(dtype, accurate) is False


def test_the_stall_exit_is_the_one_difference_of_the_two_rules():
    """A delta under the gate that stopped shrinking ends the loop with the
    exit and not without it; every other case reads the same."""
    from oni_ml_tpu.ops.stop import STALL_GATE, fp_continue

    def cont(it, delta, prev, stall):
        return bool(fp_continue(jnp.asarray(it), jnp.float32(delta),
                                jnp.float32(prev), 20, 1e-6, stall))

    stalled = (3, STALL_GATE / 10, STALL_GATE / 20)
    assert cont(*stalled, True) is False and cont(*stalled, False) is True
    for it, delta, prev in ((0, 1.0, np.inf), (3, 1e-3, 2e-3),
                            (3, 5e-7, 1e-6), (20, 1e-3, 2e-3),
                            (3, 0.5, 0.1)):
        assert cont(it, delta, prev, True) == cont(it, delta, prev, False)
