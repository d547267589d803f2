"""Config-3-at-spec demonstration: a 30-day corpus through one pre pass.

BASELINE.json config 3 is "flow LDA at scale: 30-day corpus, 50 topics,
full IP-pair vocabulary" — the shape the reference ran on a Spark
cluster (dns_pre_lda.scala:1-2 notes the cluster-scale pre stage;
SURVEY §2.2).  Round 3 demonstrated 3 days / 6M events; this tool runs
the full 30 days on one host and records the evidence an
extrapolation would otherwise stand in for:

    python tools/config3_30day.py [--events-per-day 5000000] [--days 30]
                                  [--keep] [--out JSON_PATH]

Writes 30 synthetic day files (bench._write_flow_day schema, distinct
seeds so IPs/words overlap across days the way real traffic does), runs
the runner's pre stage over the glob with the ingest-time spill, builds
the Corpus, and prints one JSON line: events, raw input bytes, peak RSS
(ru_maxrss), per-stage walls, docs/vocab of the resulting corpus.  RSS
staying a small multiple of the numeric arrays — NOT of the raw bytes —
is the claim under test (features/blob.py).

CPU-only by design: the pre stage is host code; config 3's training
number is bench.py's `lda_em_throughput_k50_v50k` phase on the chip.

Realistic-cardinality mode (a fixed host pool proves volume, not
cardinality: 150M events but only 6,000 docs / 7,127 vocab):

    python tools/config3_30day.py --ip-zipf-a 1.2 \
        --n-src 350000 --n-dst 175000 --n-svc-ports 48 --train

draws IPs from a power-law population (num_docs scales with active
IPs: >=500k documents over 30 days), widens the service-port mix
(vocab ~50k realized words), and — with --train — runs the runner's
LDA stage at K=50 over the resulting corpus, recording em_iters,
final likelihood, and the training wall alongside the pre/corpus
walls and RSS.
"""

import argparse
import json
import os
import resource
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events-per-day", type=int, default=5_000_000)
    ap.add_argument("--days", type=int, default=30)
    ap.add_argument("--out", default=None,
                    help="also write the JSON record here")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (implied by --workdir)")
    ap.add_argument("--workdir", default=None,
                    help="run in this directory instead of a fresh "
                         "tempdir; NEVER deleted (the tool only "
                         "auto-deletes directories it created)")
    # Realistic-cardinality mode: a power-law IP
    # population makes num_docs scale with active IPs (the reference's
    # two-documents-per-event mapping, flow_pre_lda.scala:366-380)
    # instead of the round-3/4 fixed 6k-host pool, and a diverse
    # service-port mix scales the realized vocabulary.
    ap.add_argument("--n-src", type=int, default=4000)
    ap.add_argument("--n-dst", type=int, default=2000)
    ap.add_argument("--ip-zipf-a", type=float, default=None,
                    help="draw IPs from a rank^-a power law over the "
                         "n-src/n-dst populations (default: uniform, "
                         "round-4 behavior)")
    ap.add_argument("--n-svc-ports", type=int, default=None,
                    help="distinct low service ports (<=1024, power-"
                         "law popularity; default: the fixed 6-service "
                         "mix)")
    ap.add_argument("--train", action="store_true",
                    help="after the corpus build, run the runner's LDA "
                         "stage (K=--num-topics) and record em_iters / "
                         "final likelihood / wall")
    ap.add_argument("--num-topics", type=int, default=50)
    ap.add_argument("--em-max-iters", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=2048)
    args = ap.parse_args()
    if args.workdir:
        args.keep = True

    # CPU-only by design (see module docstring): pin the platform
    # BEFORE any backend init, through the environment and the config
    # API both, like tests/conftest.py.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    import bench
    from oni_ml_tpu.config import (
        FeedbackConfig, LDAConfig, PipelineConfig, ScoringConfig,
    )
    from oni_ml_tpu.runner.ml_ops import run_pipeline

    work = args.workdir or tempfile.mkdtemp(prefix="oni_config3_")
    os.makedirs(work, exist_ok=True)
    rec = {"metric": "config3_30day_pre", "days": args.days,
           "events_per_day": args.events_per_day}
    try:
        # -- generate ----------------------------------------------------
        t0 = time.perf_counter()
        raw_bytes = 0
        day_files = []
        for d in range(args.days):
            path = os.path.join(work, f"flow_201601{d + 1:02d}.csv")
            with open(path, "w") as f:
                # Distinct seed per day; shared address/port space so
                # vocabulary and documents accumulate sub-linearly
                # across days (real traffic: same hosts, same services).
                bench._write_flow_day(
                    f, args.events_per_day, n_src=args.n_src,
                    n_dst=args.n_dst, seed=100 + d,
                    ip_zipf_a=args.ip_zipf_a,
                    n_svc_ports=args.n_svc_ports,
                )
            raw_bytes += os.path.getsize(path)
            day_files.append(path)
            print(f"config3: day {d + 1}/{args.days} written "
                  f"({raw_bytes / 1e9:.1f} GB total)", file=sys.stderr)
        rec["gen_wall_s"] = round(time.perf_counter() - t0, 1)
        rec["raw_gb"] = round(raw_bytes / 1e9, 2)

        # -- pre stage over the 30-file glob (ingest-time spill) ---------
        cfg = PipelineConfig(
            data_dir=work,
            flow_path=os.path.join(work, "flow_201601*.csv"),
            lda=LDAConfig(num_topics=args.num_topics,
                          em_max_iters=args.em_max_iters,
                          batch_size=args.batch_size),
            feedback=FeedbackConfig(),
            scoring=ScoringConfig(),
        )
        rec["ip_zipf_a"] = args.ip_zipf_a
        rec["n_src"], rec["n_dst"] = args.n_src, args.n_dst
        rec["n_svc_ports"] = args.n_svc_ports
        rss0_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        t1 = time.perf_counter()
        metrics = run_pipeline(cfg, "20160131", "flow", force=True,
                               stages=["pre"])
        rec["pre_wall_s"] = round(time.perf_counter() - t1, 1)
        pre = next(m for m in metrics if m.get("stage") == "pre")
        rec["events"] = pre["events"]
        rec["word_count_rows"] = pre["word_count_rows"]

        # -- corpus build (runner stage: first-seen id assignment AND
        # the words.dat/doc.dat/model.dat writes the LDA stage needs) --
        day_dir = os.path.join(work, "20160131")
        t2 = time.perf_counter()
        metrics = run_pipeline(cfg, "20160131", "flow", force=True,
                               stages=["corpus"])
        rec["corpus_wall_s"] = round(time.perf_counter() - t2, 1)
        cm = next(m for m in metrics if m.get("stage") == "corpus")
        rec["num_docs"] = cm["docs"]
        rec["vocab_size"] = cm["vocab"]
        rec["num_tokens"] = cm["tokens"]

        # -- K=num_topics training at this document cardinality --------
        if args.train:
            t3 = time.perf_counter()
            metrics = run_pipeline(cfg, "20160131", "flow", force=True,
                                   stages=["lda"])
            rec["train_wall_s"] = round(time.perf_counter() - t3, 1)
            lm = next(m for m in metrics if m.get("stage") == "lda")
            rec["num_topics"] = args.num_topics
            rec["em_iters"] = lm["em_iters"]
            rec["final_likelihood"] = lm["final_likelihood"]
            ll_path = os.path.join(day_dir, "likelihood.dat")
            if os.path.exists(ll_path):
                with open(ll_path) as f:
                    ll_lines = f.read().strip().splitlines()
                rec["likelihood_rows"] = len(ll_lines)
                rec["likelihood_last"] = ll_lines[-1] if ll_lines else None
                if args.out:
                    # The trajectory file IS the training evidence —
                    # keep it beside the record (the workdir is
                    # deleted unless --keep).
                    shutil.copyfile(
                        ll_path,
                        os.path.splitext(args.out)[0] + "_likelihood.dat",
                    )

        # ru_maxrss is KiB on Linux: binary factor, not decimal
        # (round-4 review finding: /1e6 understated the GB by 2.4%).
        peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        rec["peak_rss_gb"] = round(peak_kb * 1024 / 1e9, 2)
        rec["baseline_rss_gb"] = round(rss0_kb * 1024 / 1e9, 2)
        rec["rss_over_raw"] = round((peak_kb * 1024) / raw_bytes, 3)
        spill = os.path.join(day_dir, "raw_lines.bin")
        rec["spill_gb"] = round(os.path.getsize(spill) / 1e9, 2) \
            if os.path.exists(spill) else None
    finally:
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)

    line = json.dumps(rec)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
