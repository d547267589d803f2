"""Rules the chip bring-up (PR 21) fixed, pinned on the CPU: every
choice between paths is reported with its reason, what ran is named in
the stage records, and nothing picks a platform or a fallback behind
the caller's back."""

import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oni_ml_tpu import native_build
from oni_ml_tpu.ops import estep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# E-step dispatch: no silent fall-through
# ---------------------------------------------------------------------------


def test_auto_backend_off_tpu_reports_both_refusals():
    engine, refused = estep.resolve_backend("auto", 4096, 128, 20, 8192)
    assert engine == "xla"
    assert list(refused) == ["sparse", "pallas"]      # preference order
    assert all("backend is cpu, not tpu" == why for why in refused.values())


def test_auto_backend_on_tpu_follows_the_shape_gates(monkeypatch):
    """On a tpu backend the fused sparse kernel is preferred; a shape
    its gate refuses goes to the next engine WITH the gate's reason,
    down to XLA."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert estep.resolve_backend("auto", 4096, 128, 20, 8192) == (
        "sparse", {})
    # 191 docs per shard (a ragged mesh tail): no block divides it.
    engine, refused = estep.resolve_backend("auto", 191, 128, 20, 8192)
    assert engine == "xla"
    assert "no VMEM-feasible doc block for B=191" in refused["sparse"]
    assert "no VMEM-feasible doc block for B=191" in refused["pallas"]


def test_forced_backend_never_falls_through():
    with pytest.raises(ValueError, match="sparse E-step forced but B=191"):
        estep.resolve_backend("sparse", 191, 128, 20, 8192)
    with pytest.raises(ValueError, match="pallas E-step forced"):
        estep.resolve_backend("pallas", 191, 128, 20, 8192)
    with pytest.raises(ValueError, match="unknown E-step backend"):
        estep.resolve_backend("mosaic", 8, 16, 4, 64)
    assert estep.resolve_backend("xla", 191, 128, 20, 8192) == ("xla", {})


def test_e_step_journals_its_dispatch(tmp_path):
    """Every traced E-step says which engine it took and which gates
    refused the others: an estep_dispatch record under a recorder."""
    from oni_ml_tpu.telemetry import Journal, Recorder
    from oni_ml_tpu.telemetry.spans import use_recorder

    rng = np.random.default_rng(0)
    k, v, b, l = 3, 32, 8, 16
    lb = jnp.log(jnp.full((k, v), 1.0 / v))
    w = jnp.asarray(rng.integers(0, v, (b, l)), jnp.int32)
    c = jnp.ones((b, l), jnp.float32)
    path = str(tmp_path / "j.jsonl")
    journal = Journal(path)
    with use_recorder(Recorder(journal=journal)):
        estep.e_step(lb, jnp.float32(2.5), w, c, jnp.ones((b,)),
                     var_max_iters=3, var_tol=1e-6)
    journal.close()
    recs = [r for r in Journal.replay(path)
            if r.get("kind") == "estep_dispatch"]
    assert len(recs) == 1
    assert recs[0]["engine"] == "xla" and recs[0]["requested"] == "auto"
    assert recs[0]["shape"] == "b8.l16.k3.v32"
    assert set(recs[0]["refused"]) == {"sparse", "pallas"}


# ---------------------------------------------------------------------------
# Native libraries: built, prebuilt, or an admitted fallback
# ---------------------------------------------------------------------------


def _emit_lib(tmp_path, monkeypatch):
    """A private NativeLib over the real emit source, building into
    tmp_path, outside the package's registry."""
    from oni_ml_tpu import native_emit

    monkeypatch.setattr(native_build, "_LIBRARIES", [])
    real = native_emit._LIB
    return native_build.NativeLib(
        real._src, str(tmp_path / "liboni_emit.so"), real._configure,
        deps=real._deps,
    )


@pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")
def test_native_lib_status_built_then_prebuilt(tmp_path, monkeypatch):
    """A clean checkout has no .so: the first load compiles it
    ("built"); a second loader over the same file finds it
    ("prebuilt")."""
    lib = _emit_lib(tmp_path, monkeypatch)
    assert lib.status == "unloaded"
    assert lib.load() is not None and lib.status == "built"
    again = _emit_lib(tmp_path, monkeypatch)
    assert again.load() is not None and again.status == "prebuilt"
    assert again.name == "liboni_emit.so"


def test_native_lib_status_admits_the_fallback(tmp_path, monkeypatch):
    lib = _emit_lib(tmp_path, monkeypatch)
    monkeypatch.setenv("ONI_ML_TPU_NO_NATIVE", "1")
    assert lib.load() is None
    assert lib.status == "python-fallback" and not lib.available()


def test_load_all_names_every_library():
    statuses = native_build.load_all()
    assert set(statuses) == {"liboni_emit.so", "liboni_flow.so",
                             "liboni_dns.so", "liboni_ingest.so"}
    assert set(statuses.values()) <= {"built", "prebuilt",
                                      "python-fallback"}


# ---------------------------------------------------------------------------
# Stage records name what ran
# ---------------------------------------------------------------------------


def test_score_stage_names_scorer_and_where_the_choice_came_from(
        tmp_path, monkeypatch):
    """The batch score stage says which engine ran and whether config,
    the environment or the default chose it."""
    sys.path.insert(0, REPO)
    import chip_smoke
    from oni_ml_tpu.runner import ml_ops

    size = chip_smoke.SmokeSize(events=600, n_src=30, n_dst=20)
    day = chip_smoke.flow_day_path(str(tmp_path), size)
    monkeypatch.setenv("ONI_ML_TPU_SCORE", "device")
    argv = ["20160122", "flow", "1.1", "--flow-path", day, "--data-dir",
            str(tmp_path / "d"), "--topics", "3", "--batch-size", "32",
            "--em-max-iters", "2"]
    assert ml_ops.main(argv) == 0
    with open(tmp_path / "d" / "20160122" / "metrics.json") as f:
        recs = {r["stage"]: r for r in json.load(f) if "stage" in r}
    assert recs["score"]["scorer"] == {"value": "device", "source": "env"}
    assert recs["pre"]["featurizer"] == "native"
    comp = recs["lda"]["compile"]
    assert comp["compile_requests"] >= 1 and comp["compile_s"] > 0
    assert comp["traces"] == comp["compile_requests"] - comp["cache_hits"]
    assert recs["lda"]["plans"]["estep_kernel"]["corpus_devices"] == [0]


def test_mesh_batches_pad_to_the_sublane_tile_per_shard(monkeypatch):
    """On a data mesh every device's slice of every batch — ragged
    tails included — is a multiple of 8 rows, or the tail takes the
    Pallas kernels away from the whole run (seen on four v5e chips)."""
    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.io import Corpus
    from oni_ml_tpu.models import lda
    from oni_ml_tpu.parallel import make_mesh

    rng = np.random.default_rng(3)
    n_docs, v = 50, 40                  # 50 docs: a tail no 32 divides
    lengths = rng.integers(3, 12, n_docs)
    ptr = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lengths, out=ptr[1:])
    corpus = Corpus(
        [f"d{i}" for i in range(n_docs)], [f"w{i}" for i in range(v)], ptr,
        rng.integers(0, v, int(ptr[-1])).astype(np.int32),
        rng.integers(1, 4, int(ptr[-1])).astype(np.int32),
    )
    seen = []
    real = lda.make_batches

    def spy(*a, **kw):
        out = real(*a, **kw)
        seen.append((kw["pad_multiple"], [b.word_idx.shape[0] for b in out]))
        return out

    monkeypatch.setattr(lda, "make_batches", spy)
    cfg = LDAConfig(num_topics=3, em_max_iters=1, batch_size=32)
    mesh = make_mesh(data=4, model=1, devices=jax.devices()[:4])
    lda.train_corpus(corpus, cfg, mesh=mesh)
    lda.train_corpus(corpus, cfg)
    (pad_mesh, sizes_mesh), (pad_one, _) = seen
    assert pad_mesh == 32 and pad_one == 8
    assert all(b % 32 == 0 for b in sizes_mesh)


# ---------------------------------------------------------------------------
# Children get their platform from their caller
# ---------------------------------------------------------------------------


def test_continuous_fleet_refuses_to_pick_a_replica_platform(tmp_path):
    from oni_ml_tpu.config import PipelineConfig
    from oni_ml_tpu.runner.continuous import FleetContinuousService

    with pytest.raises(ValueError, match="--replica-platform"):
        FleetContinuousService(
            PipelineConfig(data_dir=str(tmp_path)), {"acme": "flow"},
            out_dir=str(tmp_path / "out"), replicated=2,
        )


def test_serve_smoke_tool_takes_its_platform_from_the_caller(monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serve_smoke

    with pytest.raises(TypeError, match="platform"):
        serve_smoke.run_smoke("single")
    monkeypatch.setattr(sys, "argv", ["serve_smoke.py"])
    assert serve_smoke.main() == 2
    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen["platform"] = env["JAX_PLATFORMS"]

        class P:
            returncode, stdout, stderr = 0, '{"serve_dry_run": "ok"}\n', ""

        return P

    monkeypatch.setattr(serve_smoke.subprocess, "run", fake_run)
    assert serve_smoke.run_smoke("single", platform="tpu")["rc"] == 0
    assert seen["platform"] == "tpu"


def test_compile_counters_time_what_they_count():
    """compile_counts carries the seconds jax spent lowering and
    compiling next to the request/hit counts, so a stage's steady
    share is its wall minus what it compiled."""
    from oni_ml_tpu.plans import warmup

    warmup.setup_compilation_cache()
    before = warmup.compile_counts()
    jax.block_until_ready(
        jax.jit(lambda x: jnp.cos(x) * 3.25 + x.sum())(jnp.ones((7, 5))))
    delta = warmup.counts_delta(before)
    assert delta["compile_requests"] >= 1
    assert delta["compile_s"] > 0 and delta["trace_s"] > 0
    assert delta["traces"] == (delta["compile_requests"]
                               - delta["cache_hits"])
