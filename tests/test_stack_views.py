"""A shape group's host stack is a VIEW of `make_batches`' buffer wherever
the batches lie in it end to end (`fused.stack_run`), and `np.stack`'s copy
wherever they do not: the same arrays and the same fit either way, and
`copied_bytes` says which of the two a group took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig, OnlineLDAConfig
from oni_ml_tpu.io import Batch
from oni_ml_tpu.io.corpus import Corpus, make_batches
from oni_ml_tpu.models import fused
from oni_ml_tpu.models import lda as lda_mod
from oni_ml_tpu.models.lda import train_corpus
from oni_ml_tpu.models.online_lda import OnlineLDATrainer
from oni_ml_tpu.parallel import make_mesh
from oni_ml_tpu.telemetry import spans

BF16 = jnp.dtype("bfloat16")


def _corpus(seed=0, num_terms=96):
    """Four buckets at `min_bucket_len` 8: 300 documents of up to 8 words
    (five batches of 64), 100 of 9-16 (two), 10 of 17-32 and 3 of 33-64
    (a tail each: a group of one)."""
    rng = np.random.default_rng(seed)
    lens = np.concatenate([
        rng.integers(3, 9, 300), rng.integers(9, 17, 100),
        rng.integers(17, 33, 10), rng.integers(33, 65, 3)])
    rng.shuffle(lens)
    ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    widx = np.concatenate(
        [rng.choice(num_terms, n, replace=False) for n in lens]
    ).astype(np.int32)
    counts = rng.integers(1, 4, len(widx)).astype(np.float32)
    return Corpus(doc_names=[str(i) for i in range(len(lens))],
                  vocab=[str(i) for i in range(num_terms)],
                  doc_ptr=ptr, word_idx=widx, counts=counts)


def _batches(pad_multiple=8, batch_size=64):
    return make_batches(_corpus(), batch_size=batch_size, min_bucket_len=8,
                        pad_multiple=pad_multiple)


def _copied(batches):
    return [Batch(b.word_idx.copy(), b.counts.copy(), b.doc_index,
                  b.doc_mask) for b in batches]


def _stack(batches, dtype=np.dtype(np.float32)):
    """-> (host stacks a group as handed to `put`, slots, the recorded
    events of `fit.stack` and its sub-spans)."""
    kept = []

    def put(a):
        kept.append(a)
        return jnp.asarray(a)

    rec = spans.Recorder()
    with spans.use_recorder(rec):
        groups = fused.stack_batches(batches, dtype, put)
    host = [tuple(kept[i:i + 3]) for i in range(0, len(kept), 3)]
    for dev, h in zip(groups.arrays, host):
        for d, a in zip(dev, h):
            np.testing.assert_array_equal(np.asarray(d), a)
    return host, groups.batch_slots, rec.events


def _assert_equal_to_np_stack(batches, host, slots, dtype):
    for (w, c, m), idxs in zip(host, slots):
        group = [batches[i] for i in idxs]
        for got, want in (
                (w, np.stack([b.word_idx for b in group])),
                (c, np.stack([b.counts for b in group]).astype(dtype)),
                (m, np.stack([b.doc_mask for b in group]).astype(dtype))):
            assert got.dtype == want.dtype and got.flags.c_contiguous
            np.testing.assert_array_equal(got, want)


def _counted(events):
    (stack,) = [e["args"] for e in events if e["name"] == "fit.stack"]
    copies = [e["args"] for e in events if e["name"] == "fit.stack.copy"]
    puts = [e["args"] for e in events if e["name"] == "fit.stack.put"]
    assert [e["name"] for e in events if e["name"] != "fit.stack"] == (
        ["fit.stack.copy", "fit.stack.put"] * stack["groups"])
    assert [a["bytes"] for a in copies] == [a["bytes"] for a in puts]
    assert sum(a["bytes"] for a in copies) == stack["h2d_bytes"]
    assert sum(a["copied_bytes"] for a in copies) == stack["copied_bytes"]
    return stack, copies


@pytest.mark.parametrize("batch_size,pad_multiple", [(64, 8), (64, 32)])
def test_every_group_of_make_batches_is_a_view_of_its_buffer(
        batch_size, pad_multiple):
    batches = _batches(pad_multiple, batch_size)
    host, slots, events = _stack(batches)
    sizes = sorted(len(s) for s in slots)
    assert sizes == [1, 1, 2, 5]        # two buckets, two tails of one
    assert sorted(i for s in slots for i in s) == list(range(len(batches)))
    _assert_equal_to_np_stack(batches, host, slots, np.float32)
    for (w, c, m), idxs in zip(host, slots):
        assert w.base is batches[0].word_idx.base
        assert c.base is batches[0].counts.base
        for n, i in enumerate(idxs):
            b = batches[i]
            assert np.shares_memory(w[n], b.word_idx)
            assert np.shares_memory(c[n], b.counts)
            assert w[n].ctypes.data == b.word_idx.ctypes.data
            assert c[n].ctypes.data == b.counts.ctypes.data
            assert not np.shares_memory(m, b.doc_mask)
    stack, copies = _counted(events)
    rows = sum(b.word_idx.shape[0] for b in batches)
    assert stack["copied_bytes"] == rows * 4
    assert stack["copied_bytes"] < 0.05 * stack["h2d_bytes"]
    assert [a["copied_bytes"] for a in copies] == [m.nbytes
                                                   for _, _, m in host]


FALLBACKS = {
    # name -> (the list handed to stack_batches, dtype)
    "copied": (lambda bs: _copied(bs), np.dtype(np.float32)),
    "reversed": (lambda bs: bs[::-1], np.dtype(np.float32)),
    "every_second": (lambda bs: bs[::2], np.dtype(np.float32)),
    "bfloat16": (lambda bs: bs, BF16),
    "float64": (lambda bs: bs, np.dtype(np.float64)),
}


@pytest.mark.parametrize("pad_multiple", [8, 32])
@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_batches_that_are_no_run_are_stacked_as_before(case, pad_multiple):
    change, dtype = FALLBACKS[case]
    made = _batches(pad_multiple)
    batches = change(made)
    host, slots, events = _stack(batches, dtype)
    assert max(len(s) for s in slots) >= 3
    _assert_equal_to_np_stack(batches, host, slots, dtype)
    cast = dtype != np.float32
    wrote = []
    for (w, c, m), idxs in zip(host, slots):
        # only an array that is alone in its group, or (the cast cases)
        # the word ids of an untouched list, is still a view
        view_w = len(idxs) == 1 or cast
        view_c = len(idxs) == 1 and not cast
        for n, i in enumerate(idxs):
            b = batches[i]
            assert np.shares_memory(w[n], b.word_idx) == view_w
            assert np.shares_memory(c[n], b.counts) == view_c
            assert not np.shares_memory(m, b.doc_mask)
        # and nothing shares with a batch of another group
        for j, b in enumerate(batches):
            if j not in idxs:
                assert not np.shares_memory(w, b.word_idx)
                assert not np.shares_memory(c, b.counts)
        wrote.append((0 if view_w else w.nbytes)
                     + (0 if view_c else c.nbytes) + m.nbytes)
    stack, copies = _counted(events)
    assert [a["copied_bytes"] for a in copies] == wrote
    assert stack["copied_bytes"] == sum(wrote)
    if not cast:
        # today's bytes less the second copy of the counts: the groups of
        # one are views whoever owns them
        multi = sum(w.nbytes + c.nbytes
                    for (w, c, _), s in zip(host, slots) if len(s) > 1)
        masks = sum(m.nbytes for _, _, m in host)
        assert stack["copied_bytes"] == multi + masks
    # the batches are as they were
    for b, want in zip(made, _batches(pad_multiple)):
        np.testing.assert_array_equal(b.word_idx, want.word_idx)
        np.testing.assert_array_equal(b.counts, want.counts)


def test_a_run_is_seen_only_where_it_is_one():
    buf = np.arange(48, dtype=np.float32)
    parts = [buf[i:i + 12].reshape(3, 4) for i in range(0, 48, 12)]
    run, wrote = fused.stack_run(parts)
    assert wrote == 0 and run.base is buf and run.shape == (4, 3, 4)
    np.testing.assert_array_equal(run, np.stack(parts))
    # a run that starts inside the buffer, and one array alone
    run, wrote = fused.stack_run(parts[1:3])
    assert wrote == 0 and run.ctypes.data == parts[1].ctypes.data
    np.testing.assert_array_equal(run, np.stack(parts[1:3]))
    own = np.ones((3, 4), np.float32)
    run, wrote = fused.stack_run([own])
    assert wrote == 0 and np.shares_memory(run, own)
    assert run.shape == (1, 3, 4)
    for name, arrays in {
        "a gap": [parts[0], parts[2]],
        "out of order": [parts[1], parts[0]],
        "twice the same": [parts[0], parts[0]],
        "another owner": [parts[0], own],
        "no owner": [own, own.copy()],
        "strided": [buf[0:24:2].reshape(3, 4), buf[24:48:2].reshape(3, 4)],
        "not contiguous alone": [buf[0:24:2].reshape(3, 4)],
        "another dtype": [parts[0], parts[1].view(np.int32)],
        "columns of one array": list(buf.reshape(4, 12).T.reshape(3, 4, 4)),
    }.items():
        got, wrote = fused.stack_run(arrays)
        want = np.stack(arrays)
        assert wrote == want.nbytes, name
        assert not any(np.shares_memory(got, a) for a in arrays), name
        np.testing.assert_array_equal(got, want, err_msg=name)
    # a cast is one pass, from the view or into the stack
    for arrays in (parts, [parts[0], parts[2]]):
        got, wrote = fused.stack_run(arrays, np.dtype(np.float64))
        assert got.dtype == np.float64 and wrote == got.nbytes
        np.testing.assert_array_equal(got, np.stack(arrays))


# --------------------------------------------------------------------------
# the same fit, on views and on copies
# --------------------------------------------------------------------------

def _same_fit(a, b):
    np.testing.assert_array_equal(a.gamma, b.gamma)
    np.testing.assert_array_equal(a.log_beta, b.log_beta)
    assert a.alpha == b.alpha
    assert a.likelihoods == b.likelihoods


FITS = {
    "fused_xla": dict(),
    "fused_dense": dict(dense_em="on"),
    "mesh": dict(dense_em="on", mesh=4),
    "distributed": dict(em_shards=3, distributed=True),
}


@pytest.mark.parametrize("driver", sorted(FITS))
def test_a_fit_on_views_is_the_fit_on_copied_batches(driver, monkeypatch):
    kw = dict(FITS[driver])
    call = {}
    if kw.pop("distributed", False):
        call["distributed"] = True
    data = kw.pop("mesh", None)
    if data:
        call["mesh"] = make_mesh(data=data, model=1,
                                 devices=jax.devices()[:data])
    cfg = LDAConfig(num_topics=4, alpha_init=2.5, seed=3, batch_size=64,
                    min_bucket_len=8, em_max_iters=4, em_tol=0.0, **kw)
    corpus = _corpus()

    def recorded():
        rec = spans.Recorder()
        with spans.use_recorder(rec):
            result = train_corpus(corpus, cfg, **call)
        stacks = [e["args"] for e in rec.events if e["name"] == "fit.stack"]
        return result, stacks

    views, on_views = recorded()
    monkeypatch.setattr(
        lda_mod, "make_batches",
        lambda *a, **k: _copied(make_batches(*a, **k)))
    copies, on_copies = recorded()
    _same_fit(views, copies)
    assert len(on_views) == len(on_copies) == (3 if "distributed" in call
                                               else 1)
    for v, c in zip(on_views, on_copies):
        assert v["h2d_bytes"] == c["h2d_bytes"]
        # views: the masks alone; copies: every group of two or more too
        assert v["copied_bytes"] < 0.05 * v["h2d_bytes"]
        assert (3 * v["copied_bytes"] < c["copied_bytes"]
                <= c["h2d_bytes"])


def test_online_stacked_run_is_the_stack_it_was():
    """`OnlineLDATrainer._put_stack` goes through the same helper: a run of
    `make_batches`' batches is placed as views, and is `np.stack`'s run."""
    batches = _batches()
    run = [b for b in batches if b.word_idx.shape == batches[0].word_idx.shape]
    assert len(run) >= 4
    cfg = OnlineLDAConfig(num_topics=4, batch_size=64, seed=1)
    trainer = OnlineLDATrainer(cfg, num_terms=96, total_docs=413)
    for chosen in (run[:4], _copied(run[:4]), run[1:2], run[:4:2]):
        w, c, m = trainer._put_stack(chosen)
        np.testing.assert_array_equal(
            np.asarray(w), np.stack([b.word_idx for b in chosen]))
        np.testing.assert_array_equal(
            np.asarray(c),
            np.stack([b.counts for b in chosen]).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(m),
            np.stack([b.doc_mask for b in chosen]).astype(np.float32))
        assert c.dtype == m.dtype == jnp.float32 and w.dtype == jnp.int32
    # and the updates over views are the updates over copies
    a = OnlineLDATrainer(cfg, num_terms=96, total_docs=413)
    b = OnlineLDATrainer(cfg, num_terms=96, total_docs=413)
    a.step_many(batches, chunk=4)
    b.step_many(_copied(batches), chunk=4)
    np.testing.assert_array_equal(a.log_beta(), b.log_beta())
    assert ([float(h.likelihood) for h in a.history]
            == [float(h.likelihood) for h in b.history])
