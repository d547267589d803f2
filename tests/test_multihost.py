"""Real 2-process jax.distributed training over the host-local-shards +
explicit-allreduce architecture (parallel/allreduce.py, ROADMAP item 1).

Launches two OS processes that form a CPU jax.distributed cluster; each
trains its document shards HOST-LOCALLY (its own 2 virtual devices) and
the sufficient statistics cross processes through the KV-ring allreduce
— the old global-mesh SPMD program is gone (the CPU runtime cannot
execute cross-process XLA collectives at all, which is why this whole
suite used to error).  Contracts checked: bitwise rank parity,
agreement with plain single-process training, BYTE-identical
coordinator artifacts between a 1-process and a 2-process run (the
shard plan and reduction tree derive from the corpus, not the rank
count), the sparse engine surviving distribution, and structured
failure propagation ("failed on another rank", the BackendLost/rc=3
machinery) instead of hangs or raw XLA tracebacks.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)

# The failure-injection tests rely on bounded collective waits; the
# worker fixtures inherit it too so a hung run fails the suite fast
# instead of eating the launcher timeout.
_WAIT_TIMEOUT_S = "90"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _worker_env():
    env = {
        k: v
        for k, v in os.environ.items()
        # The workers configure their own backend; scrub the suite's
        # single-process CPU/8-device env.
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ONI_ML_TPU_ALLREDUCE_TIMEOUT_S"] = _WAIT_TIMEOUT_S
    return env


def _launch_workers(outdir, nprocs: int, timeout: float = 420.0):
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(TESTS_DIR, "multihost_worker.py"),
             str(port), str(pid), str(nprocs), str(outdir)],
            env=_worker_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    for pid, out in enumerate(outs):
        assert f"WORKER_OK {pid}" in out
    return outdir


@pytest.fixture(scope="module")
def worker_runs(tmp_path_factory):
    """The 2-process cluster run."""
    return _launch_workers(tmp_path_factory.mktemp("mh2"), nprocs=2)


@pytest.fixture(scope="module")
def worker_runs_single(tmp_path_factory):
    """The SAME worker script at 1 process — the byte-identity
    baseline: same corpus-derived shard plan, same per-shard programs,
    same reduction tree, local transport."""
    return _launch_workers(tmp_path_factory.mktemp("mh1"), nprocs=1)


def test_ranks_agree_and_match_single_process(worker_runs):
    r0 = np.load(worker_runs / "proc0.npz")
    r1 = np.load(worker_runs / "proc1.npz")
    # The reduced stats are identical bytes on every rank (fixed
    # pairwise tree over gathered partials), so the whole derived model
    # must be too — parity is asserted in-loop by the trainer and
    # re-checked here on the persisted results.
    np.testing.assert_array_equal(r0["log_beta"], r1["log_beta"])
    np.testing.assert_array_equal(r0["gamma"], r1["gamma"])
    np.testing.assert_array_equal(r0["lls"], r1["lls"])
    assert r0["alpha"] == r1["alpha"]

    # And the distributed run must agree with plain (non-distributed)
    # single-process training on the same corpus/config: the explicit
    # allreduce sums the identical per-doc suff-stats, so only
    # reduction-order/batching noise remains.
    sys.path.insert(0, TESTS_DIR)
    import reference_lda as ref
    from test_lda import corpus_from_docs

    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.models import train_corpus

    docs, _ = ref.make_synthetic_corpus(
        num_docs=80, num_terms=25, num_topics=3, seed=21
    )
    res = train_corpus(
        corpus_from_docs(docs, 25),
        LDAConfig(num_topics=3, em_max_iters=6, em_tol=0.0, batch_size=32,
                  min_bucket_len=64, seed=4, fused_em_chunk=4),
        distributed=False,
    )
    np.testing.assert_allclose(res.log_beta, r0["log_beta"], atol=5e-4)
    np.testing.assert_allclose(
        np.asarray([ll for ll, _ in res.likelihoods]), r0["lls"], rtol=1e-5
    )


def test_sparse_engine_two_rank_parity(worker_runs):
    """The PR 9 sparse engine under distribution: per-shard bucketed
    layouts, per-bucket segment-sums folded into the local partials,
    [V, K] factor allreduced.  Ranks agree bit-for-bit, and the
    trajectory matches the dense-family distributed run on the same
    corpus/config within engine tolerance (the engines share
    semantics; the sparse kernel runs interpret-mode on CPU)."""
    r0 = np.load(worker_runs / "proc0.npz")
    r1 = np.load(worker_runs / "proc1.npz")
    np.testing.assert_array_equal(r0["sp_log_beta"], r1["sp_log_beta"])
    np.testing.assert_array_equal(r0["sp_gamma"], r1["sp_gamma"])
    np.testing.assert_array_equal(r0["sp_lls"], r1["sp_lls"])
    # vs the dense-family run (run 1 warm-starts while run 2 is
    # fresh-start — compare against a fresh-start dense single-process
    # run instead).
    sys.path.insert(0, TESTS_DIR)
    import reference_lda as ref
    from test_lda import corpus_from_docs

    from oni_ml_tpu.config import LDAConfig
    from oni_ml_tpu.models import train_corpus

    docs, _ = ref.make_synthetic_corpus(
        num_docs=80, num_terms=25, num_topics=3, seed=21
    )
    dense = train_corpus(
        corpus_from_docs(docs, 25),
        LDAConfig(num_topics=3, em_max_iters=6, em_tol=0.0, batch_size=32,
                  min_bucket_len=64, seed=4),
        distributed=False,
    )
    np.testing.assert_allclose(
        np.asarray([ll for ll, _ in dense.likelihoods]), r0["sp_lls"],
        rtol=1e-4,
    )
    np.testing.assert_allclose(
        np.exp(r0["sp_log_beta"]), np.exp(dense.log_beta),
        rtol=5e-3, atol=5e-3,
    )


def test_artifacts_byte_identical_across_rank_counts(
    worker_runs, worker_runs_single
):
    """THE distribution-correctness contract: a 2-rank run's
    coordinator-written artifacts are byte-identical to a 1-rank run's.
    The shard plan (and therefore every per-shard compiled program and
    the fixed pairwise reduction tree) derives from the corpus, not the
    process count — distribution changes WHERE shards run, never the
    arithmetic."""
    for day in ("day", "day_sparse"):
        for fn in ("final.beta", "final.gamma", "final.other",
                   "likelihood.dat"):
            two = (worker_runs / day / fn).read_bytes()
            one = (worker_runs_single / day / fn).read_bytes()
            assert two == one, f"{day}/{fn} differs across rank counts"


def test_streaming_checkpoint_survives_multihost(worker_runs):
    """Distributed streaming trainer: micro-batches row-split across
    ranks, lambda blended from the reduced stats identically on every
    rank; the coordinator owns a loadable stream checkpoint."""
    from oni_ml_tpu.models.online_lda import load_stream_checkpoint

    r0 = np.load(worker_runs / "proc0.npz")
    r1 = np.load(worker_runs / "proc1.npz")
    np.testing.assert_array_equal(r0["stream_lam"], r1["stream_lam"])
    assert r0["stream_steps"] == r1["stream_steps"] > 0
    z = load_stream_checkpoint(str(worker_runs / "day" / "stream.npz"))
    assert z["step"] == int(r0["stream_steps"])
    np.testing.assert_allclose(z["lam"], r0["stream_lam"], rtol=1e-6)


def test_pipeline_multihost_single_writer(worker_runs):
    """run_pipeline across both ranks: stage decisions broadcast over
    the KV store, every stage output written exactly once by the
    coordinator, full day completes (pre/corpus/lda/score all
    recorded), every rank joins stage_lda."""
    import json

    r0 = np.load(worker_runs / "proc0.npz")
    r1 = np.load(worker_runs / "proc1.npz")
    assert r0["pipeline_stages"] == 4          # coordinator ran all stages
    day = worker_runs / "20260101"
    for fn in ("word_counts.dat", "model.dat", "final.beta",
               "doc_results.csv", "word_results.csv", "flow_results.csv",
               "metrics.json"):
        assert (day / fn).exists(), fn
    metrics = json.loads((day / "metrics.json").read_text())
    stages = [m["stage"] for m in metrics
              if m.get("stage") in ("pre", "corpus", "lda", "score")]
    assert stages == ["pre", "corpus", "lda", "score"]
    score = [m for m in metrics if m.get("stage") == "score"][0]
    assert score["scored_events"] == 200
    # The lda stage record carries the shard-plan/allreduce provenance.
    lda = [m for m in metrics if m.get("stage") == "lda"][0]
    assert lda["plans"]["em_shards"]["value"] >= 2
    assert lda["plans"]["allreduce"]["transport"] == "kvring"
    assert lda["plans"]["allreduce"]["bytes_out"] > 0
    assert r1["pipeline_stages"] >= 1          # rank 1 joined stage_lda


def test_coordinator_owns_shared_files(worker_runs):
    day = worker_runs / "day"
    # Coordinator wrote the full reference output set...
    for fn in ("final.beta", "final.gamma", "final.other", "likelihood.dat"):
        assert (day / fn).exists(), fn
    # ...exactly once: likelihood.dat has one line per EM iteration (6),
    # which a second appender would have doubled.
    lines = (day / "likelihood.dat").read_text().strip().split("\n")
    assert len(lines) == 6, lines
    # The completed run cleaned its checkpoint (coordinator-gated).
    assert not (day / "checkpoint.npz").exists()


def test_shard_plan_journaled(worker_runs):
    """The day dir's run journal carries the {"kind": "shard_plan"}
    record (and allreduce records) for post-hoc reconstruction."""
    import json

    jpath = worker_runs / "20260101" / "run_journal.jsonl"
    kinds = [json.loads(line).get("kind")
             for line in jpath.read_text().splitlines() if line.strip()]
    assert "shard_plan" in kinds
    assert "allreduce" in kinds


_ABORT_WORKER = r"""
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from oni_ml_tpu.parallel import initialize_distributed
initialize_distributed(f"localhost:{port}", 2, pid)
from oni_ml_tpu.config import LDAConfig, PipelineConfig, ScoringConfig
from oni_ml_tpu.runner.ml_ops import run_pipeline
cfg = PipelineConfig(
    data_dir=sys.argv[3], flow_path="/nonexistent/flow.csv",
    lda=LDAConfig(num_topics=3), scoring=ScoringConfig(threshold=0.5),
)
run_pipeline(cfg, "20260102", "flow")
"""


_RANK1_FAIL_WORKER = r"""
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from oni_ml_tpu.parallel import initialize_distributed
initialize_distributed(f"localhost:{port}", 2, pid)
import numpy as np
from oni_ml_tpu.config import LDAConfig, PipelineConfig, ScoringConfig
from oni_ml_tpu.runner import ml_ops

flow = os.path.join(sys.argv[3], "flow.csv")
if pid == 0:
    rows = ["hdr"] + [
        ",".join(["0"]*4 + ["1","2","3","0",f"10.0.0.{i%5}",f"10.0.1.{i%3}",
                  "443","2000","0","0","0","0","5","100"] + ["0"]*9)
        for i in range(64)
    ]
    with open(flow, "w") as f:
        f.write("\n".join(rows) + "\n")
if pid == 1:
    # Fail BEFORE any collective inside the lda stage — the class of
    # failure only the failure-key relay / outcome barrier can surface
    # on the peer.
    def boom(ctx):
        raise OSError("rank1 cannot read shared model.dat")
    ml_ops._STAGE_FNS[ml_ops.Stage.LDA] = boom
cfg = PipelineConfig(
    data_dir=sys.argv[3], flow_path=flow,
    lda=LDAConfig(num_topics=3, em_max_iters=3, batch_size=32,
                  min_bucket_len=64),
    scoring=ScoringConfig(threshold=0.5),
)
ml_ops.run_pipeline(cfg, "20260103", "flow")
"""


def _run_pair(script, tmp_path, timeout=180):
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(port), str(pid),
             str(tmp_path)],
            env=_worker_env(), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)  # hang == the bug
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def test_noncoordinator_precollective_failure_fails_all_ranks(tmp_path):
    """Rank 1 raising inside stage_lda before its collectives must fail
    the whole job, not hang it: the failing rank posts the failure key
    and enters the outcome barrier; the survivor sees the False flag
    (or the key itself from inside a collective wait) and aborts with
    the structured peer-failure error.  Both ranks must terminate
    nonzero within the timeout."""
    procs, outs = _run_pair(_RANK1_FAIL_WORKER, tmp_path)
    assert procs[0].returncode != 0, outs[0][-2000:]
    assert procs[1].returncode != 0, outs[1][-2000:]
    assert "failed on another rank" in outs[0]


def test_coordinator_stage_failure_fails_all_ranks(tmp_path):
    """A stage exception on the coordinator (bad flow_path) must
    surface on every rank as the structured "failed on another rank"
    peer-failure (a BackendLost subclass — ml_ops exits rc=3 with the
    structured payload) — not leave non-coordinators blocked in the
    next decision broadcast, and not a raw XLA traceback."""
    procs, outs = _run_pair(_ABORT_WORKER, tmp_path)
    assert procs[0].returncode != 0, outs[0][-2000:]
    assert procs[1].returncode != 0, outs[1][-2000:]
    assert "failed on another rank" in outs[1]
    assert "XlaRuntimeError" not in outs[1]
