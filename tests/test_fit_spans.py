"""The fit's layer boundaries inside the program (telemetry/spans.py): the
span tree a Recorder sees, the same spans in jax's profiler, the sweep
counter from the kernels to LDAResult, and what "tracing off" costs."""

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oni_ml_tpu.config import LDAConfig
from oni_ml_tpu.io.corpus import Corpus, make_batches
from oni_ml_tpu.models import lda as lda_mod
from oni_ml_tpu.models.lda import train_corpus
from oni_ml_tpu.ops import dense_estep
from oni_ml_tpu.telemetry import roofline, spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# driver -> config overrides, the fit.* children it must show once / twice
DRIVERS = {
    "fused_dense": (dict(estep_engine="dense"),
                    ["fit.engine", "fit.batches", "fit.plan", "fit.stack",
                     "fit.densify", "fit.runner", "fit.teardown"]),
    "fused_xla": (dict(),
                  ["fit.engine", "fit.batches", "fit.plan", "fit.stack",
                   "fit.runner", "fit.teardown"]),
    "stepwise": (dict(fused_em_chunk=1),
                 # fit.roofline: the stepwise driver's cost harvest under a
                 # Recorder; fit.teardown: dropping the trainer's own jitted
                 # E-step (24 ms of a 0.4 s fit on the CPU, which lay under
                 # no span and left the share below one preemption from
                 # its bound)
                 ["fit.engine", "fit.batches", "fit.stack", "fit.roofline",
                  "fit.teardown"]),
}


def _corpus(num_docs=150, num_terms=96, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 30, num_docs)
    ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    widx = np.concatenate(
        [rng.choice(num_terms, n, replace=False) for n in lens]
    ).astype(np.int32)
    counts = rng.integers(1, 4, len(widx)).astype(np.float32)
    return Corpus(doc_names=[str(i) for i in range(num_docs)],
                  vocab=[str(i) for i in range(num_terms)],
                  doc_ptr=ptr, word_idx=widx, counts=counts)


def _config(**kw):
    base = dict(num_topics=4, batch_size=32, em_max_iters=4, em_tol=1e-12)
    return LDAConfig(**dict(base, **kw))


def _batches(corpus, cfg):
    return make_batches(
        corpus, batch_size=cfg.batch_size,
        min_bucket_len=cfg.min_bucket_len, pad_multiple=8)


def _padded_rows(corpus, cfg):
    return sum(b.word_idx.shape[0] for b in _batches(corpus, cfg))


@pytest.fixture(scope="module")
def recorded_fits():
    """driver -> (result, recorder events) of TWO fits under one Recorder."""
    out = {}
    corpus = _corpus()
    for name, (kw, _) in DRIVERS.items():
        rec = spans.Recorder()
        with spans.use_recorder(rec):
            train_corpus(corpus, _config(**kw))
            result = train_corpus(corpus, _config(**kw))
        out[name] = (result, sorted(rec.events, key=lambda e: e["start_ns"]))
    return out


# parent span -> the sub-spans that may lie under it, at depth 2
SUBSPANS = {"fit.stack": ("fit.stack.copy", "fit.stack.put"),
            "fit.readback": ("fit.readback.d2h", "fit.readback.scatter"),
            # the corpus store's gate (PR 41)
            "fit.plan": ("fit.plan.gate",)}
# A gap between spans is held as a share of what they tile AND in
# absolute terms: a warm 10 ms CPU fit lost 10.3% to 1 ms of interpreter
# time once in four whole runs of the suite.
GAP_NS = 5_000_000


def _tiles(parts, whole):
    covered = sum(e["dur_ns"] for e in parts)
    return whole["dur_ns"] - covered <= max(0.1 * whole["dur_ns"], GAP_NS)


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_one_fit_root_per_fit_and_every_layer_boundary_under_it(
        recorded_fits, driver):
    _, events = recorded_fits[driver]
    roots = [e for e in events if e["name"] == "fit"]
    assert len(roots) == 2
    assert len({e["id"] for e in events}) == len(events)
    by_id = {e["id"]: e for e in events}
    sub_names = {n for names in SUBSPANS.values() for n in names}
    for root in roots:
        assert root["parent"] is None and root["root"] == root["id"]
        assert root["depth"] == 0
        family = [e for e in events
                  if e["root"] == root["id"] and e is not root]
        children = [e for e in family if e["name"] not in sub_names]
        names = [e["name"] for e in children]
        for once in DRIVERS[driver][1]:
            assert names.count(once) == 1, (once, names)
        # the trainer and fit(); gamma and beta
        assert names.count("fit.init") == 2
        assert names.count("fit.readback") == 2
        assert "em.host_sync" in names
        if driver != "stepwise":
            assert "em.run_chunk" in names
        # direct children at depth 1 ...
        for e in children:
            assert e["parent"] == root["id"] and e["depth"] == 1, e
            assert root["start_ns"] <= e["start_ns"]
            assert (e["start_ns"] + e["dur_ns"]
                    <= root["start_ns"] + root["dur_ns"])
        assert _tiles(children, root), (root["dur_ns"], names)
        # ... sub-spans at depth 2, inside a parent that may hold them
        for e in family:
            if e["name"] in sub_names:
                parent = by_id[e["parent"]]
                assert e["depth"] == 2 and parent["depth"] == 1, e
                assert e["name"] in SUBSPANS[parent["name"]], e
                assert parent["start_ns"] <= e["start_ns"]
                assert (e["start_ns"] + e["dur_ns"]
                        <= parent["start_ns"] + parent["dur_ns"])
    # the two fits do not share a root
    assert roots[0]["id"] != roots[1]["id"]


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_stack_and_readback_are_tiled_by_their_sub_spans(recorded_fits,
                                                         driver):
    """`fit.stack` = a copy and a put a shape group; `fit.readback` = a
    transfer and a scatter a device array of gamma, one transfer of beta:
    the same vocabulary in all three drivers (the stepwise driver reads a
    batch at a time and stacks nothing)."""
    result, events = recorded_fits[driver]
    corpus, cfg = _corpus(), _config()
    batches = _batches(corpus, cfg)
    k = cfg.num_topics
    root = [e for e in events if e["name"] == "fit"][-1]

    def under(parent):
        return [e for e in events if e["parent"] == parent["id"]]

    def of(name):
        return [e for e in events
                if e["name"] == name and e["root"] == root["id"]]

    (stack,) = of("fit.stack")
    subs = under(stack)
    if driver == "stepwise":
        assert subs == []
        arrays = len(batches)
    else:
        arrays = stack["args"]["groups"]
        assert [e["name"] for e in subs] == (
            ["fit.stack.copy", "fit.stack.put"] * arrays)
        assert _tiles(subs, stack)
        copies, puts = subs[0::2], subs[1::2]
        assert ([e["args"]["bytes"] for e in copies]
                == [e["args"]["bytes"] for e in puts])
        assert (sum(e["args"]["bytes"] for e in copies)
                == stack["args"]["h2d_bytes"]
                == sum(b.word_idx.nbytes + b.counts.nbytes
                       + b.doc_mask.astype(np.float32).nbytes
                       for b in batches))
        assert {e["args"]["shards"] for e in puts} == {1}
        # make_batches' groups are views of its buffers: the host wrote
        # the masks and nothing else
        masks = [sum(b.doc_mask.nbytes for b in batches
                     if b.word_idx.shape == shape)
                 for shape in sorted({b.word_idx.shape for b in batches})]
        assert [e["args"]["copied_bytes"] for e in copies] == masks
        assert stack["args"]["copied_bytes"] == sum(masks) == (
            _padded_rows(corpus, cfg) * 4)

    gamma, beta = of("fit.readback")
    assert (gamma["args"]["what"], beta["args"]["what"]) == (
        "gamma", "log_beta")
    subs = under(gamma)
    assert [e["name"] for e in subs] == (
        ["fit.readback.d2h", "fit.readback.scatter"] * arrays)
    assert _tiles(subs, gamma)
    d2h, scatter = subs[0::2], subs[1::2]
    # float32 as it left the device, padding included; float64 written
    assert sum(e["args"]["bytes"] for e in d2h) == (
        _padded_rows(corpus, cfg) * k * 4)
    assert sum(e["args"]["rows"] for e in scatter) == corpus.num_docs
    assert sum(e["args"]["bytes"] for e in scatter) == result.gamma.nbytes
    (only,) = under(beta)
    assert only["name"] == "fit.readback.d2h" and _tiles([only], beta)
    assert only["args"]["bytes"] == k * corpus.num_terms * 4
    assert only["args"]["shards"] == 1


def test_the_distributed_driver_reads_back_through_the_same_sub_spans():
    corpus, cfg = _corpus(), _config(em_max_iters=1, em_shards=3)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        result = train_corpus(corpus, cfg, distributed=True)
    by_id = {e["id"]: e for e in rec.events}
    subs = [e for e in rec.events if e["name"].startswith("fit.readback.")]
    assert {e["name"] for e in subs} == {"fit.readback.d2h",
                                         "fit.readback.scatter"}
    for e in subs:
        assert by_id[e["parent"]]["name"] == "fit.readback" and e["depth"] == 2
    scatters = [e["args"] for e in subs
                if e["name"] == "fit.readback.scatter"]
    assert sum(a["rows"] for a in scatters) == corpus.num_docs
    assert sum(a["bytes"] for a in scatters) == result.gamma.nbytes
    stacks = [e for e in rec.events if e["name"] == "fit.stack"]
    assert len(stacks) == 3             # one a document shard
    for stack in stacks:
        inside = [e["name"] for e in rec.events if e["parent"] == stack["id"]]
        assert inside == ["fit.stack.copy", "fit.stack.put"] * (
            stack["args"]["groups"])
        copies = [e["args"] for e in rec.events
                  if e["parent"] == stack["id"]
                  and e["name"] == "fit.stack.copy"]
        assert (sum(a["bytes"] for a in copies)
                == stack["args"]["h2d_bytes"])
        # a shard's batches are make_batches' own: views, masks written
        assert (sum(a["copied_bytes"] for a in copies)
                == stack["args"]["copied_bytes"]
                < 0.1 * stack["args"]["h2d_bytes"])


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_fit_span_counts_what_the_fit_ran(recorded_fits, driver):
    result, events = recorded_fits[driver]
    root = [e for e in events if e["name"] == "fit"][-1]
    args = root["args"]
    assert args["num_docs"] == 150 and args["num_terms"] == 96
    assert args["k"] == 4 and args["mesh"] is None
    assert args["engine"] == "dense"
    assert args["em_iters"] == result.em_iters == 4
    assert args["doc_sweeps"] == result.doc_sweeps
    assert args["vi_max"] == result.vi_max
    syncs = [e for e in events
             if e["name"] == "em.host_sync" and e["root"] == root["id"]]
    assert sum(e["args"]["steps"] for e in syncs) == 4
    assert sum(e["args"]["doc_sweeps"] for e in syncs) == result.doc_sweeps
    assert max(e["args"]["vi_max"] for e in syncs) == result.vi_max
    batches = [e for e in events if e["name"] == "fit.batches"][-1]
    assert batches["args"]["rows"] == _padded_rows(_corpus(), _config())
    if driver != "stepwise":
        first = [e["args"]["first"] for e in events
                 if e["name"] == "em.run_chunk" and e["root"] == root["id"]]
        assert first[0] is True and not any(first[1:])
        assert args["kernel"] == result.plan["estep_kernel"]["value"]


@pytest.mark.parametrize("driver", ["fused_dense", "fused_xla"])
def test_fit_runner_counts_the_batches_read_from_their_stack_in_place(
        recorded_fits, driver):
    """Every fit through the chunk runner says how its batches reach their
    E-step: `stack_indexed_batches` (the kernel reads them out of their
    group's stack in place: dense groups of two batches or more) and
    `sliced_batches` (the rest) add up to `batches`."""
    _, events = recorded_fits[driver]
    by_shape = {}
    for b in _batches(_corpus(), _config()):
        by_shape[b.word_idx.shape] = by_shape.get(b.word_idx.shape, 0) + 1
    stacked = sum(n for n in by_shape.values() if n >= 2)
    # two groups of three here; a fit with single-batch groups beside a
    # stacked one is counted in tests/test_sharded.py
    assert stacked == 6
    runners = [e["args"] for e in events if e["name"] == "fit.runner"]
    assert len(runners) == 2
    for args in runners:
        assert args["batches"] == sum(by_shape.values())
        assert args["stack_indexed_batches"] == (
            stacked if driver == "fused_dense" else 0)
        assert (args["stack_indexed_batches"] + args["sliced_batches"]
                == args["batches"])


# site of `fit.batches` -> (config overrides, train_corpus keywords)
BATCH_SITES = {
    "make_batches": (dict(), dict()),
    "bucketed": (dict(estep_engine="sparse", sparse_min_bucket_len=16),
                 dict()),
    "distributed": (dict(em_shards=3), dict(distributed=True)),
    "distributed_bucketed": (
        dict(em_shards=3, estep_engine="sparse", sparse_min_bucket_len=16),
        dict(distributed=True)),
}


@pytest.mark.parametrize("site", sorted(BATCH_SITES))
def test_fit_batches_counts_the_tokens_placed_and_the_padded_cells(site):
    """`tokens` is the corpus' nnz and `cells` the sum of B * L, beside
    `batches`, `rows` and `shapes`, at every site that batches a fit."""
    cfg_kw, train_kw = BATCH_SITES[site]
    corpus, cfg = _corpus(), _config(em_max_iters=1, **cfg_kw)
    rec = spans.Recorder()
    handed = []
    real_fit = lda_mod.LDATrainer.fit

    def spy_fit(self, batches, *a, **kw):
        handed.extend(batches)
        return real_fit(self, batches, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lda_mod.LDATrainer, "fit", spy_fit)
        with spans.use_recorder(rec):
            train_corpus(corpus, cfg, **train_kw)
    (span,) = [e for e in rec.events if e["name"] == "fit.batches"]
    args = span["args"]
    assert args["tokens"] == len(corpus.word_idx) == int(corpus.doc_ptr[-1])
    assert args["cells"] == sum(b.word_idx.size for b in handed)
    assert args["batches"] == len(handed)
    assert args["rows"] == sum(b.word_idx.shape[0] for b in handed)
    assert args["shapes"] == len({b.word_idx.shape for b in handed})
    assert args["tokens"] < args["cells"]
    # every real token sits in exactly one cell
    assert sum(int((b.counts > 0).sum()) for b in handed) == args["tokens"]


# site that batches a fit with make_batches -> (config overrides,
# train_corpus keywords, data shards)
TAIL_SITES = {
    "make_batches": (dict(), dict(), 1),
    "distributed": (dict(em_shards=2), dict(distributed=True), 1),
    "data_mesh": (dict(), dict(mesh=4), 4),
}


@pytest.mark.parametrize("site", sorted(TAIL_SITES))
def test_fit_batches_counts_the_rows_the_tail_rule_added(site):
    """`tail_pad_rows`: the rows `dense_estep.tail_rows` padded an under-full
    bucket's batch to beyond the 8-row sublane tile a shard, summed over
    the batches: here a bucket of 1,100 short documents (over 256 rows a
    shard, so its batch pads to the 128-lane tile) and one of 20 long
    ones (under 256: the 8-row tile alone)."""
    cfg_kw, train_kw, data = TAIL_SITES[site]
    rng = np.random.default_rng(4)
    lens = np.concatenate([rng.integers(3, 16, 1100), rng.integers(20, 30, 20)])
    ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    corpus = Corpus(doc_names=[str(i) for i in range(len(lens))],
                    vocab=[str(i) for i in range(96)], doc_ptr=ptr,
                    word_idx=rng.integers(0, 96, int(ptr[-1])).astype(np.int32),
                    counts=np.ones(int(ptr[-1]), np.float32))
    cfg = _config(em_max_iters=1, batch_size=2048, **cfg_kw)
    if "mesh" in train_kw:
        from oni_ml_tpu.parallel import make_mesh

        train_kw = dict(mesh=make_mesh(data=data, model=1,
                                       devices=jax.devices()[:data]))
    rec = spans.Recorder()
    handed = []
    real_fit = lda_mod.LDATrainer.fit

    def spy_fit(self, batches, *a, **kw):
        handed.extend(batches)
        return real_fit(self, batches, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lda_mod.LDATrainer, "fit", spy_fit)
        with spans.use_recorder(rec):
            train_corpus(corpus, cfg, **train_kw)
    (span,) = [e for e in rec.events if e["name"] == "fit.batches"]
    sublane = 8 * data
    want = 0
    for b in handed:
        n = int(b.doc_mask.sum())
        assert n < cfg.batch_size               # every bucket under-full
        want += b.word_idx.shape[0] - -(-n // sublane) * sublane
    assert want > 0
    assert span["args"]["tail_pad_rows"] == want


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_doc_sweeps_lies_between_one_sweep_and_the_cap(recorded_fits, driver):
    result, _ = recorded_fits[driver]
    cfg = _config()
    rows = _padded_rows(_corpus(), cfg)
    assert rows * result.em_iters <= result.doc_sweeps
    assert result.doc_sweeps <= rows * result.em_iters * cfg.var_max_iters
    assert 1 <= result.vi_max <= cfg.var_max_iters
    assert isinstance(result.doc_sweeps, int)


def _corpus_with_a_feedback_doc(tokens=300):
    """_corpus() plus one document of one word in a cell of 300: as
    `tokens` count-1 tokens of it (raw counts of 1, a row sum of 300), or
    with `tokens` 1 as a Corpus from pre-LDA's sum by (IP, word) holds
    it, one entry of 300."""
    base = _corpus()
    return Corpus(
        doc_names=base.doc_names + ["feedback"], vocab=base.vocab,
        doc_ptr=np.append(base.doc_ptr, base.doc_ptr[-1] + tokens),
        word_idx=np.append(base.word_idx, np.full(tokens, 5, np.int32)),
        counts=np.append(base.counts,
                         np.full(tokens, 300 / tokens, np.float32)))


@pytest.mark.parametrize("precision,feedback,cell_scan,stored", [
    ("f32", True, "none", jnp.float32),
    ("bf16", False, "bounds", jnp.bfloat16),
    ("bf16", True, "exact", jnp.int16),
])
def test_fit_plan_says_what_the_storage_gate_read(precision, feedback,
                                                  cell_scan, stored):
    # at f32 the gate reads the Corpus' counts, one entry a cell (the
    # ladder, which the bf16 cases climb, sums repeated tokens)
    corpus = (_corpus_with_a_feedback_doc(1 if precision == "f32" else 300)
              if feedback else _corpus())
    cfg = _config(estep_engine="dense", dense_precision=precision,
                  em_max_iters=1)
    batches = _batches(corpus, cfg)
    padded_tokens = sum(b.word_idx.size for b in batches)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        result = train_corpus(corpus, cfg)
    plan, = [e["args"] for e in rec.events if e["name"] == "fit.plan"]
    gate, = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    densify, = [e["args"] for e in rec.events if e["name"] == "fit.densify"]
    assert plan["cell_scan"] == cell_scan
    # the products the kernels take, keyed to the day's cells and not to
    # the store, and the phinorm's MXU passes a sweep: packed where a cell
    # passes 256, on either store (K = 4: 24 rows of contraction, one
    # pass), XLA's DEFAULT elsewhere
    products = "split6_packed" if feedback else "default"
    assert (plan["products"], plan["phinorm_passes"]) == (products, 1)
    assert result.plan["products"] == {"value": products,
                                       "phinorm_passes": 1}
    assert densify["dense_bytes"] == (
        _padded_rows(corpus, cfg) * dense_estep.padded_width(corpus.num_terms)
        * jnp.dtype(stored).itemsize)
    if cell_scan == "none":
        assert plan["scan_tokens"] == 0
    elif cell_scan == "bounds":
        assert plan["scan_tokens"] == 2 * padded_tokens
    else:
        # the feedback row, the only one over 256, read twice more: once
        # for the repeat test, once for its summed cells
        row_len, = [b.word_idx.shape[1] for b in batches
                    if (b.counts.sum(axis=1) > 256).any()]
        assert plan["scan_tokens"] == 2 * padded_tokens + 2 * row_len
        assert 2 * padded_tokens < plan["scan_tokens"] < 3 * padded_tokens
    # at f32 the store reads no token, and the Corpus' largest count says
    # whether a cell passes 256: its one entry of 300, no row undecided;
    # under bf16 the feedback row of repeated tokens is the one over 256
    # and the one that repeats
    assert (gate["undecided_rows"], gate["repeat_rows"]) == (
        (1, 1) if feedback and precision == "bf16" else (0, 0))
    assert gate["accurate"] == int(feedback)
    # the ladder's rung, and no raw count over 256 in its pass (none at
    # f32, and under bf16 the feedback cell is 300 count-1 tokens); at
    # f32 the Corpus' largest count, the cell of 300
    assert (gate["rung"], gate["over_bf16_tokens"]) == (
        dense_estep.store_rung(stored), 0)
    assert gate.get("largest_count") == (
        300 if precision == "f32" and feedback else None)
    assert (densify["store"], densify["itemsize"]) == (
        jnp.dtype(stored).name, jnp.dtype(stored).itemsize)


def _corpus_with_hub_docs(hubs=3, feedback=False, num_terms=320, seed=1):
    """_corpus() at `num_terms` words plus `hubs` documents of 300
    DISTINCT words at count 1 (row sums of 300, every cell 1), as a day
    aggregated per (IP, word) gives them; with `feedback`, one document
    of 300 count-1 tokens of one word besides."""
    base = _corpus(num_terms=num_terms)
    rng = np.random.default_rng(seed)
    docs = [rng.choice(num_terms, 300, replace=False) for _ in range(hubs)]
    if feedback:
        docs.append(np.full(300, 5))
    lens = np.cumsum([len(d) for d in docs])
    return Corpus(
        doc_names=base.doc_names + [f"hub{i}" for i in range(len(docs))],
        vocab=base.vocab,
        doc_ptr=np.append(base.doc_ptr, base.doc_ptr[-1] + lens),
        word_idx=np.concatenate([base.word_idx] + docs).astype(np.int32),
        counts=np.append(base.counts, np.ones(int(lens[-1]), np.float32)))


@pytest.mark.parametrize("feedback,stored,repeats", [
    (False, jnp.bfloat16, 0),
    (True, jnp.int16, 1),
])
def test_the_gate_sums_cells_only_in_rows_where_a_word_repeats(
        feedback, stored, repeats, monkeypatch):
    """Hub documents of distinct words sum over 256 yet are decided
    without one summed cell; the feedback document, the one row where a
    word repeats, is the only row the summed-cell reader sees."""
    corpus = _corpus_with_hub_docs(feedback=feedback)
    cfg = _config(estep_engine="dense", dense_precision="bf16",
                  em_max_iters=1)
    reader = dense_estep.max_dense_cell
    summed = []

    def reader_of_repeats(word_idx, counts):
        if not feedback:
            raise AssertionError("rows of distinct words were summed")
        summed.append(np.shape(word_idx)[0])
        return reader(word_idx, counts)

    monkeypatch.setattr(dense_estep, "max_dense_cell", reader_of_repeats)
    rec = spans.Recorder()
    with spans.use_recorder(rec):
        train_corpus(corpus, cfg)
    gate, = [e["args"] for e in rec.events if e["name"] == "fit.plan.gate"]
    densify, = [e["args"] for e in rec.events if e["name"] == "fit.densify"]
    assert (gate["store"], gate["cell_scan"]) == (jnp.dtype(stored).name,
                                                  "exact")
    assert gate["undecided_rows"] == 3 + feedback
    assert gate["repeat_rows"] == repeats
    assert summed == [repeats] * bool(repeats)
    assert densify["dense_bytes"] == (
        _padded_rows(corpus, cfg) * dense_estep.padded_width(corpus.num_terms)
        * jnp.dtype(stored).itemsize)


def test_fused_and_stepwise_drivers_count_the_same_sweeps():
    """With a cap the fixed point cannot reach, both drivers sweep every
    batch to it; at the stock tolerance their counts differ by a few
    batch-sweeps at most (the two programs round differently)."""
    corpus = _corpus()
    capped = dict(var_max_iters=3, var_tol=1e-12, warm_start_gamma=False)
    fused = train_corpus(corpus, _config(**capped))
    step = train_corpus(corpus, _config(fused_em_chunk=1, **capped))
    rows = _padded_rows(corpus, _config())
    assert fused.doc_sweeps == step.doc_sweeps == rows * 4 * 3
    assert fused.vi_max == step.vi_max == 3
    fused = train_corpus(corpus, _config())
    step = train_corpus(corpus, _config(fused_em_chunk=1))
    assert fused.doc_sweeps == pytest.approx(step.doc_sweeps, rel=0.02)
    assert fused.doc_sweeps < rows * 4 * _config().var_max_iters


@pytest.mark.parametrize("wmajor", [False, True])
def test_dense_kernel_sweeps_are_the_sum_over_blocks_of_sweeps_times_rows(
        wmajor):
    """Two doc blocks, hand-checked: the first holds documents of one word
    (their fixed point is reached at once), the second documents of many
    words; each block alone says how often IT swept."""
    k, v, bb = 4, 128, 128 if wmajor else 8
    b = 2 * bb
    rng = np.random.default_rng(3)
    dense = np.zeros((b, v), np.float32)
    dense[:bb, 0] = 1.0                           # block 0: one word each
    dense[bb:] = rng.integers(0, 3, (bb, v))      # block 1: many words
    mask = np.ones((b,), np.float32)
    beta = rng.dirichlet(np.full(v, 0.1), size=k).astype(np.float32)
    fp = (dense_estep.dense_fixed_point_w if wmajor
          else dense_estep.dense_fixed_point)

    def run(rows):
        block = jnp.asarray(dense[rows].T if wmajor else dense[rows])
        out = fp(jnp.asarray(beta), jnp.asarray(2.5, jnp.float32), block,
                 jnp.asarray(mask[rows]), 20, 1e-6, block=bb, interpret=True)
        return int(out[4]), int(out[5])

    first, first_sweeps = run(slice(0, bb))
    second, second_sweeps = run(slice(bb, b))
    assert first != second and min(first, second) >= 1
    assert (first_sweeps, second_sweeps) == (first * bb, second * bb)
    both, sweeps = run(slice(0, b))
    assert both == max(first, second)
    assert sweeps == (first + second) * bb        # not max x rows
    assert sweeps < both * b


def test_maybe_span_without_recorder_or_profiler_is_the_shared_noop():
    assert spans.current_recorder() is None
    with spans.maybe_span("fit", num_docs=1) as sp:
        sp.annotate(doc_sweeps=3)
    assert sp is spans._NO_SPAN
    with spans.maybe_span("fit.batches") as other:
        pass
    assert other is sp


def test_spans_module_and_the_noop_span_import_no_jax():
    code = (
        "import sys\n"
        "from oni_ml_tpu.telemetry.spans import Recorder, maybe_span\n"
        "with maybe_span('fit', a=1) as sp:\n"
        "    sp.annotate(b=2)\n"
        "rec = Recorder()\n"
        "with rec.span('fit') as sp:\n"
        "    sp.annotate(b=2)\n"
        "assert rec.events[0]['args'] == {'b': 2}\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print('ok')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr


def test_recorded_span_and_its_journal_line_carry_id_parent_and_root(
        tmp_path):
    from oni_ml_tpu.telemetry.journal import Journal

    path = str(tmp_path / "j.jsonl")
    journal = Journal(path)
    rec = spans.Recorder(journal=journal)
    with rec.span("fit"):
        with rec.span("fit.plan") as sp:
            sp.annotate(kernel="xla")
            with rec.span("deeper"):
                pass
        with rec.span("fit.stack"):
            pass
    with rec.span("fit"):
        pass
    journal.close()
    lines = {r["id"]: r for r in Journal.replay(path) if r["kind"] == "span"}
    by_name = {}
    for r in lines.values():
        by_name.setdefault(r["name"], []).append(r)
    first, second = sorted(by_name["fit"], key=lambda r: r["id"])
    plan, stack, deeper = (by_name[n][0]
                           for n in ("fit.plan", "fit.stack", "deeper"))
    assert first["parent"] is None and first["root"] == first["id"]
    assert second["root"] == second["id"] != first["id"]
    assert plan["parent"] == stack["parent"] == first["id"]
    assert deeper["parent"] == plan["id"]
    assert {plan["root"], stack["root"], deeper["root"]} == {first["id"]}
    assert (first["depth"], plan["depth"], deeper["depth"]) == (0, 1, 2)
    assert plan["args"] == {"kernel": "xla"}
    assert {"id", "parent", "root", "depth"} <= set(rec.events[0])


def test_a_fit_under_the_profiler_puts_its_spans_in_the_trace(tmp_path):
    """No Recorder: the profiler session alone switches the spans on, and
    the benchmark's loader reads them back with their stats."""
    sys.path.insert(0, ROOT)
    try:
        from benchmarks.harness import program_trace
        from benchmarks.jobs import fit_spans, fit_tail
    finally:
        sys.path.remove(ROOT)
    corpus, cfg = _corpus(), _config(estep_engine="dense")
    train_corpus(corpus, cfg)                      # compile outside the trace
    assert spans.current_recorder() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:fit"):
            result = train_corpus(corpus, cfg)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(files) == 1
    loaded = program_trace.load(files[0], fit_spans.SPANS)
    by_name = {}
    for name, start, dur, stats, line in loaded["spans"]:
        by_name.setdefault(name, []).append((start, dur, stats, line))
    for name in ("fit", "fit.engine", "fit.batches", "fit.plan", "fit.stack",
                 "fit.densify", "fit.runner", "em.run_chunk", "em.host_sync"):
        assert len(by_name[name]) == 1, name
    assert len(by_name["fit.init"]) == len(by_name["fit.readback"]) == 2
    fit_start, fit_dur, fit_stats, fit_line = by_name["fit"][0]
    assert fit_stats["num_docs"] == 150 and fit_stats["k"] == 4
    for name, rows in by_name.items():
        for start, dur, _, line in rows:
            assert line == fit_line
            assert fit_start <= start and start + dur <= fit_start + fit_dur
    counts = by_name["fit.counts"][0][2]
    assert counts["em_iters"] == result.em_iters == 4
    assert counts["doc_sweeps"] == result.doc_sweeps
    assert counts["kernel"] == result.plan["estep_kernel"]["value"]
    assert by_name["fit.batches.counts"][0][2]["rows"] == _padded_rows(
        corpus, cfg)
    placed = by_name["fit.batches.counts"][0][2]
    assert placed["tokens"] == len(corpus.word_idx)
    assert placed["cells"] == sum(
        b.word_idx.size for b in _batches(corpus, cfg))
    assert by_name["em.host_sync.counts"][0][2]["steps"] == 4
    plan = by_name["fit.plan.counts"][0][2]
    assert (plan["cell_scan"], plan["scan_tokens"]) == ("none", 0)
    assert (plan["products"], plan["phinorm_passes"]) == ("default", 1)
    assert by_name["fit.densify"][0][2]["groups"] >= 1
    assert by_name["fit.densify.counts"][0][2]["dense_bytes"] > 0
    assert by_name["em.run_chunk"][0][2]["first"] in (1, "True", True)
    runner = by_name["fit.runner.counts"][0][2]
    assert runner["batches"] == len(_batches(corpus, cfg))
    assert (runner["stack_indexed_batches"] + runner["sliced_batches"]
            == runner["batches"])
    # the sub-spans, as the tail's readers load them
    tail = {}
    for name, start, dur, stats, line in program_trace.load_spans(
            files[0], fit_tail.SPANS):
        assert line == fit_line
        tail.setdefault(name, []).append((start, dur, stats))
    groups = by_name["fit.stack"][0][2]["groups"]
    for name, n in (("fit.stack.copy", groups), ("fit.stack.put", groups),
                    ("fit.readback.d2h", groups + 1),
                    ("fit.readback.scatter", groups)):
        assert len(tail[name]) == len(tail[name + ".counts"]) == n, name
    (stack,) = tail["fit.stack"]
    for start, dur, _ in tail["fit.stack.copy"] + tail["fit.stack.put"]:
        assert stack[0] <= start and start + dur <= stack[0] + stack[1]
    assert sum(c[2]["bytes"] for c in tail["fit.stack.put.counts"]) == (
        tail["fit.stack.counts"][0][2]["h2d_bytes"])
    # the same counters under the profiler: the masks, and nothing else
    assert sum(c[2]["copied_bytes"]
               for c in tail["fit.stack.copy.counts"]) == (
        tail["fit.stack.counts"][0][2]["copied_bytes"]) == (
        _padded_rows(corpus, cfg) * 4)
    assert sum(c[2]["rows"] for c in tail["fit.readback.scatter.counts"]
               ) == corpus.num_docs
    assert {c[2]["shards"] for c in tail["fit.readback.d2h.counts"]} == {1}


def test_em_roofline_record_rests_on_the_counted_sweeps():
    corpus, cfg = _corpus(), _config(estep_engine="dense")
    rec = spans.Recorder()
    since = roofline.emit_count()
    with spans.use_recorder(rec):
        result = train_corpus(corpus, cfg)
    record = [r for r in roofline.emitted_records(since)
              if r["phase"] == "em.run_chunk"][-1]
    rows = _padded_rows(corpus, cfg)
    width = dense_estep.padded_width(corpus.num_terms)
    assert record["doc_sweeps"] == result.doc_sweeps
    assert record["effective_flops"] == pytest.approx(
        (4.0 * result.doc_sweeps + 2.0 * rows * result.em_iters)
        * width * cfg.num_topics)
    assert record["effective_flops_per_s"] > 0
    assert "em.chunk_dispatches" not in rec.snapshot()["counters"]
    assert rec.snapshot()["histograms"]["span.em.run_chunk_s"]["count"] >= 1
